"""One run of one cell: set-up, the measured window, the check, the trace.

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``
gives its configuration (``configs/<config>.json``), its traffic
(``traffic/<traffic>.json``) and its chips; the configuration names its
model reference (``models/<reference>.py``: weights' shapes, loss and
gradient, counts); the limits of its check are in ``limits/<cell>.json``;
each per-layer metric is read by ``metrics/<metric>.py``.  Adding any of
these is adding files.

The system under test is the FetchSGD mesh trainer:
``repro.launch.steps.make_train_step`` on a ``data = chips, model = 1``
mesh, compiled once.  Set-up makes the weights from the seed, then drives
the compiled step through the cell's first rounds with the window's own
call and feed; the reference follows those rounds once the window is over.
"""

from __future__ import annotations

import dataclasses
import functools
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

import counts
import reference
import tracing
import traffic

HERE = Path(__file__).resolve().parent
CACHE_DIR = HERE / ".jax_cache"

def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# -- finding a cell's files -----------------------------------------------------

def load_cell(name: str, bench: dict, root: Path = HERE) -> dict:
    """The cell's entry with its configuration, model reference, traffic
    and limits."""
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = dict(cells[name])
    cfg_path = root / "configs" / f"{cell['config']}.json"
    cfg = json.loads(cfg_path.read_text())
    if "reference" not in cfg:
        raise KeyError(f"{cfg_path} names no model reference: give it "
                       f'"reference": "<name>" of models/<name>.py')
    model = model_module(cfg["reference"], root)
    tr = traffic.load(cell["traffic"], root)
    if tr["clients"] != cell["chips"]:
        raise ValueError(f"{name}: traffic {cell['traffic']} has "
                         f"{tr['clients']} clients for {cell['chips']} chips")
    limits = json.loads((root / "limits" / f"{name}.json").read_text())
    return {"cell": cell, "cfg": cfg, "model": model, "tr": tr,
            "limits": limits}


def metric_entries(bench: dict, cell: str, group: str) -> list:
    """The metrics of ``group`` that this cell reports."""
    return [m for m in bench[group]
            if "workloads" not in m or cell in m["workloads"]]


def _module(path: Path, name: str):
    """The module of the file ``path``; its directory goes on the import
    path, so that files beside it can import each other."""
    if str(path.parent) not in sys.path:
        sys.path.insert(0, str(path.parent))
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str, root: Path = HERE):
    """The ``read(ctx)`` function of ``metrics/<name>.py``."""
    return _module(root / "metrics" / f"{name}.py", f"metric_{name}").read


def model_module(name: str, root: Path = HERE):
    """The model reference ``models/<name>.py`` (its contract is in
    ``models/decoder.py``)."""
    path = root / "models" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no model reference {path}")
    return _module(path, f"model_{name}")


# -- devices --------------------------------------------------------------------

def devices(chips: int, require_chip: bool):
    """The first ``chips`` devices; fails off a TPU or with too few."""
    import jax
    devs = jax.devices()
    if require_chip and devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devs[0].platform!r} devices; "
                         f"this benchmark runs on the chip only")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} devices, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def enable_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


# -- the system under test -------------------------------------------------------

def program_config(cfg: dict):
    """The program's ArchConfig, with every size of the file applied."""
    from repro import configs
    base = configs.get_config(cfg["program_arch"])
    fields = {f.name for f in dataclasses.fields(base)}
    return dataclasses.replace(
        base, **{k: v for k, v in cfg["model"].items() if k in fields})


# Faults planted under the timed path for the tests and ``study.py``: each
# maps the weights before and after a step to the weights the step keeps.
def _flip_sign(old, new):
    """The update applied with the wrong sign."""
    return 2 * old - new


def _move_ids(old, new):
    """The update applied one element further on."""
    import jax.numpy as jnp
    return old + jnp.roll(new - old, 1)


PLANTED = {"sign_flip": _flip_sign, "moved_ids": _move_ids}


def sparse_change(a: dict, b: dict, n_max: int) -> list:
    """Per leaf of ``a - b``: the number of elements that differ, and the
    first ``n_max`` of their offsets with the differences there.  FetchSGD
    changes at most ``k`` weights a round, so after the checked rounds this
    holds the whole change."""
    import jax.numpy as jnp
    out = []
    for x, y in zip(reference.leaves(a), reference.leaves(b)):
        diff = (x - y).reshape(-1)
        idx = jnp.flatnonzero(diff, size=n_max, fill_value=0)
        out.append((jnp.count_nonzero(diff), idx, diff[idx]))
    return out


def host_change(parts: list) -> dict:
    """:func:`sparse_change`'s output on the host, as one sparse vector:
    ``leaf`` and ``offset`` of each changed weight and its ``value``;
    ``complete`` is False where a leaf changed more than ``n_max``."""
    import jax
    import numpy as np
    leaf, offset, value, complete = [], [], [], True
    for i, (n, idx, v) in enumerate(jax.device_get(parts)):
        m = min(int(n), len(idx))
        complete &= int(n) <= len(idx)
        leaf.append(np.full(m, i))
        offset.append(np.asarray(idx[:m], np.int64))
        value.append(np.asarray(v[:m], np.float64))
    return {"leaf": np.concatenate(leaf), "offset": np.concatenate(offset),
            "value": np.concatenate(value), "leaves": len(parts),
            "complete": complete}


class Program:
    """The compiled train step on the cell's mesh, and its state; its
    weights are ``model``'s, made from the seed."""

    def __init__(self, model, cfg: dict, tr: dict, chips: int,
                 fault: str | None):
        import jax
        import jax.numpy as jnp
        from repro.core import fetchsgd as F
        from repro.launch import mesh as mesh_lib, shapes, steps
        self.jax, self.fault = jax, fault
        if fault == "no_exchange":     # each chip keeps its own table
            from repro.fed import aggregator
            aggregator.mesh_aggregate = lambda table, *a, **k: table
        fs = F.FetchSGDConfig(
            rows=tr["rows"], cols=tr["cols"], k=tr["k"],
            momentum=tr["momentum"], error_mode=tr["error_mode"],
            momentum_masking=tr["momentum_masking"], impl=tr["sketch_impl"])
        mesh = mesh_lib.make_mesh((chips, 1), ("data", "model"))
        shape = shapes.ShapeSpec("bench", "train", tr["seq_len"],
                                 tr["clients"] * tr["seqs_per_client"])
        bundle = steps.make_train_step(program_config(cfg), shape, mesh, fs,
                                       aggregate=tr["merge"])
        want = jax.tree.map(lambda s: (s.shape, s.dtype), bundle.inputs[0])
        mine = jax.tree.map(lambda s: (s.shape, s.dtype), jax.eval_shape(
            lambda: reference.init_params(model, cfg["model"],
                                          jax.random.PRNGKey(0))))
        if want != mine:
            raise ValueError("the benchmark's weights do not match the "
                             "program's parameter tree")
        self.step = bundle.fn.lower(*bundle.inputs).compile()
        p_sh, o_sh, self.batch_sh, _ = self.step.input_shardings[0]
        self.init = jax.jit(
            lambda key: reference.init_params(model, cfg["model"], key),
            out_shardings=p_sh)
        self.opt0 = jax.device_put(F.init_state(fs), o_sh)
        self.lr = jnp.float32(tr["lr"])
        self.seqs = tr["seqs_per_client"]
        self.change = jax.jit(functools.partial(
            sparse_change, n_max=tr["k"] * tr["check_rounds"]))
        self.planted = {name: jax.jit(functools.partial(jax.tree.map, f))
                        for name, f in PLANTED.items()}
        self.params = self.opt = None

    def start(self, seed: int) -> None:
        self.params = self.init(reference.seed_key(seed))
        self.opt = self.opt0

    def round(self, tokens, labels):
        """One federated round: put the cohort's batch, step, return the
        loss (left on the device)."""
        jax = self.jax
        if self.fault == "half_batch":
            tokens, labels = (_first_half(x, self.seqs)
                              for x in (tokens, labels))
        with jax.profiler.TraceAnnotation("round.put_batch"):
            batch = jax.device_put({"tokens": tokens, "labels": labels},
                                   self.batch_sh)
        with jax.profiler.TraceAnnotation("round.dispatch"):
            params, opt, m = self.step(self.params, self.opt, batch, self.lr)
        if self.fault in self.planted:
            params = self.planted[self.fault](self.params, params)
        if self.fault != "unchanged":
            self.params, self.opt = params, opt
        with jax.profiler.TraceAnnotation("round.wait"):
            jax.block_until_ready(self.params)
        return m["loss"]

    def free(self) -> None:
        self.params = self.opt = self.opt0 = self.step = None
        gc.collect()


def _first_half(x, seqs: int):
    """Each client's batch with its second half replaced by its first."""
    x = x.reshape(-1, seqs, x.shape[-1]).copy()
    h = seqs // 2
    x[:, h:2 * h] = x[:, :h]
    return x.reshape(-1, x.shape[-1])


def checked_rounds(prog: Program, batches: list) -> dict:
    """Set-up: the first rounds from fresh weights, through the window's own
    call; keeps what the check compares."""
    p0 = prog.params
    losses, su_first = [], None
    for tokens, labels in batches:
        losses.append(prog.round(tokens, labels))
        if su_first is None:
            su_first = prog.jax.device_get(prog.opt.momentum_sketch)
    change = host_change(prog.change(prog.params, p0))
    return {"losses": [float(x) for x in losses], "su_first": su_first,
            "change": change}


def window(prog: Program, pool: list, seconds: float) -> dict:
    """Rounds until ``seconds`` have passed, ending at a round boundary."""
    losses, t0 = [], time.perf_counter()
    while True:
        tokens, labels = pool[len(losses) % len(pool)]
        losses.append(prog.round(tokens, labels))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            return {"losses": losses, "seconds": elapsed}


# -- the check ------------------------------------------------------------------

def reference_readings(model, cfg: dict, tr: dict, seed: int,
                       batches: list, q=None, sketch_q=None,
                       log=None) -> dict:
    """The plain reference through the checked rounds, on one device, with
    ``model`` as the model; ``q`` and ``sketch_q`` put a lower precision
    in (see :func:`reference.fetchsgd_rounds`)."""
    import jax
    mcfg = cfg["model"]
    p0 = jax.jit(lambda k: reference.init_params(model, mcfg, k))(
        reference.seed_key(seed))
    out = reference.fetchsgd_rounds(p0, batches, model, mcfg, tr, q=q,
                                    sketch_q=sketch_q, log=log)
    change = jax.jit(functools.partial(
        sparse_change, n_max=tr["k"] * len(batches)))
    out["change"] = host_change(change(out.pop("params"), p0))
    return out


def compare(got: dict, ref: dict) -> dict:
    """The readings, each relative to the reference.

    * ``loss_gap``: the widest gap of a checked round's loss;
    * ``sketch_gap``, ``sketch_diff``: the first gradient as the optimizer
      got it: per row of the momentum sketch after the first round, over
      the cells neither side zeroed, the gap of its norm and the norm of
      the difference, each over the reference's norm; the worst row
      (1 where fewer than half the reference's cells remain);
    * ``change_gap``, ``change_diff``: the weight change after the checked
      rounds as one sparse vector: the gap of its norm, and the norm of its
      difference from the reference's, which a wrong sign or a wrong
      element fails;
    * ``change_leaf_gap``: the worst leaf's gap of the change's norm,
      against the larger of its norm and the median leaf's.
    ``limits/<cell>.json`` says which are compared."""
    import numpy as np
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["losses"],
                                                   ref["losses"]))
    a = np.asarray(got["su_first"], np.float64)
    b = np.asarray(ref["su_first"], np.float64)
    both = (a != 0) & (b != 0)
    a, b = np.where(both, a, 0.0), np.where(both, b, 0.0)
    nb = np.sqrt((b ** 2).sum(1))
    lost = both.sum(1) < 0.5 * (ref["su_first"] != 0).sum(1)
    with np.errstate(divide="ignore", invalid="ignore"):
        gap = np.abs(np.sqrt((a ** 2).sum(1)) - nb) / nb
        diff = np.sqrt(((a - b) ** 2).sum(1)) / nb
    sketch_gap = float(np.max(np.where(lost, 1.0, gap)))
    sketch_diff = float(np.max(np.where(lost, 1.0, diff)))
    return {"loss_gap": loss, "sketch_gap": sketch_gap,
            "sketch_diff": sketch_diff,
            **_change_readings(got["change"], ref["change"])}


def _change_readings(ca: dict, cb: dict) -> dict:
    import numpy as np
    if not ca["complete"]:           # more weights moved than k a round
        return {"change_gap": math.inf, "change_diff": math.inf,
                "change_leaf_gap": math.inf}
    leaf_a = np.sqrt(np.bincount(ca["leaf"], ca["value"] ** 2, ca["leaves"]))
    leaf_b = np.sqrt(np.bincount(cb["leaf"], cb["value"] ** 2, cb["leaves"]))
    total_a, total_b = math.hypot(*leaf_a), math.hypot(*leaf_b)
    keys = np.concatenate([np.stack([c["leaf"], c["offset"]], 1)
                           for c in (ca, cb)])
    _, where = np.unique(keys, axis=0, return_inverse=True)
    delta = np.bincount(where.reshape(-1), np.concatenate(
        [ca["value"], -cb["value"]]))
    moved = leaf_b[leaf_b > 0]
    med = float(np.median(moved)) if moved.size else 0.0
    leaf = max((abs(x - y) / max(y, med) if max(y, med) else
                (0.0 if x == 0 else math.inf))
               for x, y in zip(leaf_a, leaf_b))
    if not total_b:
        return {"change_gap": math.inf, "change_diff": math.inf,
                "change_leaf_gap": leaf}
    return {"change_gap": abs(total_a - total_b) / total_b,
            "change_diff": float(np.sqrt((delta ** 2).sum())) / total_b,
            "change_leaf_gap": leaf}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Correct when every compared number is within its limit."""
    compared = {k: {"value": numbers[k], "limit": lim}
                for k, lim in limits["limits"].items()}
    ok = all(v["value"] <= v["limit"] for v in compared.values())
    return ok, compared


# -- one run --------------------------------------------------------------------

def run(spec: dict, bench: dict, seed: int, seconds: float, trace: bool,
        t_start: float, *, require_chip: bool = True,
        fault: str | None = None, cache: bool = True) -> dict:
    """One run of the cell; returns the result line as a dict.

    The tests drive it off the chip (``require_chip=False``), without the
    compile cache, and with a ``fault`` planted under the timed path."""
    import jax
    cell, cfg, model, tr = (spec[k] for k in ("cell", "cfg", "model", "tr"))
    if seed < 0:
        raise ValueError("the seed is a whole number >= 0")
    devs = devices(cell["chips"], require_chip)
    if cache:
        enable_cache()
    vocab = cfg["model"]["vocab"]
    batches = traffic.rounds(tr, vocab, seed)
    check, pool = batches[:tr["check_rounds"]], batches[tr["check_rounds"]:]

    prog = Program(model, cfg, tr, cell["chips"], fault)
    prog.start(seed)
    got = checked_rounds(prog, check)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s; checked losses {got['losses']}")

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    win = window(prog, pool, seconds)
    if trace:
        jax.profiler.stop_trace()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    win_losses = [float(x) for x in jax.device_get(win["losses"])]
    failed = sum(not math.isfinite(x) for x in win_losses)
    prog.free()
    del prog

    t_ref = time.perf_counter()
    ref = reference_readings(model, cfg, tr, seed, check, log=log)
    numbers = compare(got, ref)
    ok, compared = judge(numbers, spec["limits"])
    log(f"reference {time.perf_counter() - t_ref:.3f} s; losses "
        f"{ref['losses']}; readings {json.dumps(numbers)}")

    rounds = len(win_losses)
    tokens = traffic.tokens_per_round(tr)
    kind = devs[0].device_kind
    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    e2e = {"round_s": win["seconds"] / rounds,
           "tokens_per_s": rounds * tokens / win["seconds"],
           "peak_hbm_bytes": peak, "setup_s": setup_s}
    result = {"correct": ok and failed == 0, "attempted": rounds,
              "failed": failed}
    if not trace:
        result["metrics"] = {
            m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
            for m in metric_entries(bench, cell["name"], "end_to_end")}
    else:
        ops, spans = tracing.load(trace_dir)
        lo, hi = tracing.window(spans)
        ops = tracing.clip(ops, lo, hi)
        busy = tracing.busy_seconds(ops)
        ctx = {"ops": ops, "spans": spans, "window_s": hi - lo,
               "busy_s": busy, "rounds": rounds, "chips": cell["chips"],
               "round_s": win["seconds"] / rounds, "config": cfg,
               "cfg": cfg["model"], "model": model, "tr": tr,
               "peak": counts.peaks(kind)}
        metrics = {}
        for m in metric_entries(bench, cell["name"], "per_layer"):
            value = reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        device.update(busy_s=busy, window_s=hi - lo)
        result["breakdown"] = tracing.breakdown(ops, spans, lo, hi)
        shutil.rmtree(trace_dir, ignore_errors=True)
    result["device"] = device
    result["compared"] = compared
    for name, v in compared.items():
        log(f"compared {name} {v['value']!r} limit {v['limit']!r}")
    return result
