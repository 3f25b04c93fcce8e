"""The one traffic generator: seeded federated client batches.

A traffic mix is a JSON file under ``traffic/`` (found by the name a cell
gives), and this module is the only code that reads one.  Its keys:

* ``clients``: clients per round, one per chip (a cross-silo cohort);
* ``seqs_per_client``, ``seq_len``: each client's local batch;
* ``n_classes``, ``follow_prob``: the non-i.i.d. split below;
* ``pool_rounds``: rounds of distinct batches drawn before the window;
  the window cycles through them, so every seed does the same work;
* ``check_rounds``: the first rounds, which the reference follows;
* ``rows``, ``cols``, ``k``, ``lr``, ``momentum``, ``error_mode``,
  ``momentum_masking``, ``merge``, ``sketch_impl``: FetchSGD's settings.

The client data is the paper's pathological non-i.i.d. split (FetchSGD,
arXiv:2007.07682, Sec. 5.1) over tokens: each client holds one latent
class, a class is a Markov chain whose successor table is drawn from the
seed, and each step follows the chain with probability ``follow_prob``.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
KEYS = ("clients", "seqs_per_client", "seq_len", "n_classes", "follow_prob",
        "pool_rounds", "check_rounds", "rows", "cols", "k", "lr", "momentum",
        "error_mode", "momentum_masking", "merge", "sketch_impl")


def load(name: str, root: Path = HERE) -> dict:
    tr = json.loads((root / "traffic" / f"{name}.json").read_text())
    missing = [k for k in KEYS if k not in tr]
    if missing:
        raise ValueError(f"traffic {name}: missing keys {missing}")
    return tr


def client_batch(tr: dict, vocab: int, seed: int, client: int):
    """(tokens, labels), each (seqs_per_client, seq_len) int32."""
    cls = client % tr["n_classes"]
    succ = np.random.default_rng([seed, cls]).integers(0, vocab, vocab)
    rng = np.random.default_rng([seed, tr["n_classes"], client])
    n, S = tr["seqs_per_client"], tr["seq_len"]
    toks = np.empty((n, S + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, n)
    follow = rng.random((S, n)) < tr["follow_prob"]
    noise = rng.integers(0, vocab, (S, n))
    for t in range(S):
        toks[:, t + 1] = np.where(follow[t], succ[toks[:, t]], noise[t])
    return toks[:, :-1], toks[:, 1:]


def round_batch(tr: dict, vocab: int, seed: int, rnd: int):
    """The cohort's batch of round ``rnd``: its clients' rows stacked, so
    that chip ``i`` of the mesh gets client ``rnd * clients + i``."""
    parts = [client_batch(tr, vocab, seed, rnd * tr["clients"] + i)
             for i in range(tr["clients"])]
    return (np.concatenate([p[0] for p in parts]),
            np.concatenate([p[1] for p in parts]))


def rounds(tr: dict, vocab: int, seed: int) -> list:
    """Every distinct round batch of a run: the checked rounds first."""
    n = tr["check_rounds"] + tr["pool_rounds"]
    return [round_batch(tr, vocab, seed, r) for r in range(n)]


def tokens_per_round(tr: dict) -> int:
    return tr["clients"] * tr["seqs_per_client"] * tr["seq_len"]
