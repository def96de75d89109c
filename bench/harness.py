"""One run of one benchmark cell: set-up, the measured window, the check.

A cell is found by name: ``workloads/<cell>.json`` (mode, splits,
aggregation backend, plan source, fan-outs, batch, the limits of its check)
names its configuration ``configs/<config>.json`` (model and graph), whose
``model`` names ``models/<model>.py`` (the model's weights, plain reference
layer and work count). The per-layer metrics a cell reports are the
``per_layer`` entries of ``BENCHMARK.json`` that list it, each read by
``metrics/<metric>.py`` (``registry``). A later cell, configuration, model
or metric is a new file and a new entry.

The run:

1. set-up: the configuration's graph (``graphgen``), the program's
   ``Trainer``, weights from the seed (``reference.init_params``) handed to
   it; three steps through ``train_epoch`` whose blocks, gradient and
   parameters the check keeps; then one warm-up epoch, which compiles (or
   loads from the cache) every shape of the window.
2. the window: whole epochs through ``train_epoch`` until ``seconds`` have
   passed, replaying the warm-up epoch (the same batches, padded to
   shapes compiled already); every step ends in the trainer's
   ``device_get``. With
   ``trace`` the profiler records the window and the program's own spans
   are on, and each step program the window runs is kept (``StepPrograms``)
   so that its ops' named scopes can be read (``trace_reduce.op_paths``).
3. the check: once the window is closed, its peak memory read and the
   program's state freed, the plain reference trains the same three blocks
   from the same weights, at the configuration's matmul precision
   (``correct``).

The harness drives the program through a few of the ``Trainer``'s private
attributes (``program_attr``): a program change that renames one stops the
run, so it never measures something else.

The step's optimized HLO text, which names each instruction's scope in its
``op_name`` metadata, comes from the jitted step that ``_dispatch_step``
runs (``_step_fn`` or ``_cached_step_fn``): lowered on the arguments of its
first call in the run, then ``Lowered.compile().as_text()`` once the window
has closed. The compile is the one the call made, so it comes from JAX's
caches; on a TPU v5e with the persistent cache warm it loads in 0.2 s and
its text is the cold compile's, ``op_name`` metadata included (chip run).
An XLA dump flag would miss every program loaded from the cache.
"""
from __future__ import annotations

import gc
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CHECK_STEPS = 3
WINDOW_MARK = "bench_window"


class NoChip(SystemExit):
    """The run needs an accelerator that JAX does not find."""


def log(msg: str) -> None:
    print(msg, flush=True)


def program_attr(obj, name: str):
    """``obj.name``, an attribute of the program that the harness reads or
    sets; a program without it stops the run."""
    if not hasattr(obj, name):
        raise AttributeError(
            f"bench: the program's {type(obj).__name__} has no {name!r}")
    return getattr(obj, name)


def set_program_attr(obj, name: str, value) -> None:
    program_attr(obj, name)
    setattr(obj, name, value)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict]:
    cell = load_json(BENCH / "workloads" / f"{name}.json")
    return cell, load_json(BENCH / "configs" / f"{cell['config']}.json")


def load_metric(name: str):
    """The reader module ``metrics/<name>.py``."""
    from bench import registry

    return registry.load("metrics", name)


def declared(cell_name: str, bench: dict) -> tuple[list, list]:
    """The end-to-end and per-layer metric entries that the cell reports."""
    e2e = [m for m in bench["end_to_end"]
           if cell_name in m.get("workloads", [cell_name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if cell_name in m.get("workloads", [cell_name] if m["moves"] in names else [])]
    return e2e, layer


def require_devices(chips: int):
    """The JAX devices, after checking for ``chips`` TPU chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"bench: needs a TPU; JAX runs on {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips; JAX found {len(devices)}")
    return devices


# --------------------------------------------------------------------------- #
# program side
# --------------------------------------------------------------------------- #
class BlockRecorder:
    """Wraps the trainer's sampler: keeps the first ``CHECK_STEPS`` blocks of
    epoch 0 whole, and the sizes of every block, keyed by (epoch, batch)."""

    def __init__(self, sampler, mode: str):
        self.blocks, self.sizes = {}, {}
        self._lock = threading.Lock()
        cls = type(sampler)  # the unwrapped methods: a later recorder replaces this one
        if mode == "split":
            orig = program_attr(cls, "sample_batch").__get__(sampler)
            set_program_attr(sampler, "sample_batch",
                             lambda t, e, i: self._keep(e, i, orig(t, e, i)))
        else:
            orig = program_attr(cls, "sample_micro_batch").__get__(sampler)
            set_program_attr(sampler, "sample_micro_batch",
                             lambda t, p, e, i: self._keep_all(e, i, orig(t, p, e, i)))

    def _keep_all(self, epoch, index, samples):
        if len(samples) != 1:
            raise ValueError("the check needs one block per batch")
        self._keep(epoch, index, samples[0])
        return samples

    def _keep(self, epoch, index, sample):
        from bench.counts import block_sizes

        block = {
            "frontiers": list(sample.frontiers),
            "layers": [(layer.src, layer.dst) for layer in sample.layers],
        }
        with self._lock:
            self.sizes[(epoch, index)] = block_sizes(block)
            if epoch == 0 and index < CHECK_STEPS:
                self.blocks[index] = block
        return sample


class StepPrograms:
    """The step programs the trainer dispatches: each one lowered on the
    arguments of its first call, and which of them ran while ``in_window``."""

    def __init__(self, tr):
        import jax

        self.lowered, self.window, self.in_window = {}, set(), False
        dispatch = program_attr(type(tr), "_dispatch_step").__get__(tr)

        def record(fn, *args):
            leaves, tree = jax.tree_util.tree_flatten((tr.params, tr.opt_state, args))
            key = (id(fn), tree, tuple(map(jax.typeof, leaves)))
            if key not in self.lowered:
                self.lowered[key] = fn.lower(tr.params, tr.opt_state, *args)
            if self.in_window:
                self.window.add(key)
            return dispatch(fn, *args)

        set_program_attr(tr, "_dispatch_step", record)

    def compiled(self) -> list[tuple[str, str]]:
        """(module name, optimized HLO text) of each program that ran in the
        window."""
        from bench.trace_reduce import module_name

        texts = [self.lowered[key].compile().as_text() for key in self.window]
        return [(module_name(text), text) for text in texts]


def make_trainer(cfg: dict, cell: dict, graph, trace: bool):
    """The program's ``Trainer`` for the cell on the configuration's graph.

    Its offline stage (presample, partition) is seeded by the graph's seed:
    the dataset and its partition are the same in every run."""
    from repro.graph.csr import CSRGraph
    from repro.graph.datasets import DatasetSpec, GraphDataset
    from repro.models.gnn import GNNSpec
    from repro.train.trainer import TrainConfig, Trainer

    spec = DatasetSpec(
        cfg["name"], num_nodes=graph.num_nodes, avg_degree=float(cfg["avg_degree"]),
        feat_dim=int(cfg["feat_dim"]), num_classes=int(cfg["num_classes"]),
        train_fraction=float(cfg["train_fraction"]),
    )
    ds = GraphDataset(
        spec=spec, graph=CSRGraph(graph.indptr, graph.indices),
        features=graph.features, labels=graph.labels, train_ids=graph.train_ids,
    )
    model = GNNSpec(
        model=cfg["model"], in_dim=int(cfg["feat_dim"]),
        hidden_dim=int(cfg["hidden_dim"]), out_dim=int(cfg["num_classes"]),
        num_layers=int(cfg["num_layers"]), num_heads=int(cfg["num_heads"]),
        agg_backend=cell["agg_backend"], dtype=cfg["dtype"],
    )
    tcfg = TrainConfig(
        mode=cell["mode"], num_devices=int(cell["num_devices"]),
        fanouts=tuple(cell["fanouts"]), batch_size=int(cell["batch_size"]),
        lr=float(cfg["lr"]), optimizer=cfg["optimizer"],
        plan_source=cell["plan_source"], plan_workers=int(cell["plan_workers"]),
        pipeline_depth=int(cell["pipeline_depth"]), trace_recompiles=True,
        obs_trace=trace, seed=int(cfg["graph_seed"]),
        # the split plan's partition weights; dp presamples nothing
        **({"presample_epochs": int(cell["presample_epochs"])}
           if cell["mode"] == "split" else {}),
    )
    return Trainer(ds, model, tcfg)


def start(tr, cfg: dict, cell: dict, seed: int):
    """Seed a trainer's run: the batch order and neighbour samples from
    ``seed`` (the sampler's keyed draws), weights from ``seed`` with a fresh
    optimizer state, and a recorder of the blocks. Returns the initial
    weights on the host and the recorder."""
    import jax
    from bench import reference

    set_program_attr(tr.sampler, "seed", seed)
    set_program_attr(tr.sampler, "rng", np.random.default_rng(seed))
    params0 = reference.init_params(cfg, seed)
    set_program_attr(tr, "params", params0)
    set_program_attr(tr, "opt_state", tr.opt.init(params0))
    set_program_attr(tr, "_epoch", 0)
    set_program_attr(tr, "global_step", 0)
    return jax.device_get(params0), BlockRecorder(tr.sampler, cell["mode"])


def first_steps(tr, cfg: dict, params0) -> dict:
    """Drive the trainer through its first ``CHECK_STEPS`` steps with the
    window's own call; keep each loss, the first gradient (from Adam's first
    moment after one step) and the parameters after the last step."""
    import jax

    kept = {}
    sync = program_attr(tr, "_sync_step")

    def sync_and_keep(*args):
        out = sync(*args)
        if "m1" not in kept:
            kept["m1"] = jax.device_get(tr.opt_state.slots["m"])
        return out

    tr._sync_step = sync_and_keep
    try:
        ep = tr.train_epoch(max_iters=CHECK_STEPS)
    finally:
        del tr._sync_step
    b1 = float(cfg["adam_b1"])
    return {
        "losses": [it.loss for it in ep.iters],
        "grad1": jax.tree_util.tree_map(lambda m: np.asarray(m) / (1 - b1), kept["m1"]),
        "params": jax.device_get(tr.params),
        "misses": int(ep.recompiles.get("misses", 0)),
    }


# --------------------------------------------------------------------------- #
# the run
# --------------------------------------------------------------------------- #
def run(workload: str, seed: int, seconds: float, trace: bool, *,
        t_start: float) -> dict:
    """One run of the declared cell ``workload`` on the chips it asks for;
    returns the result line's object."""
    import jax

    cell, cfg = load_cell(workload)
    devices = require_devices(cell["chips"])
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    # every program of the run, however quick to compile, is cached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return run_cell(workload, cell, cfg, devices, device_peaks(devices[0].device_kind),
                    seed, seconds, trace, t_start=t_start)


def run_cell(workload: str, cell: dict, cfg: dict, devices: list, peaks: dict,
             seed: int, seconds: float, trace: bool, *, t_start: float) -> dict:
    """The run of ``run`` on the given cell, configuration, devices and
    peaks (tests give small shapes, the CPU and made-up peaks)."""
    import jax

    bench = load_json(ROOT / "BENCHMARK.json") if (ROOT / "BENCHMARK.json").exists() else None
    sys.path.insert(0, str(ROOT / "src"))
    from bench import correct, graphgen, reference

    dev = devices[0]
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    def phase(name, t):
        log(f"setup: {name} {time.perf_counter() - t!r} s")

    t = time.perf_counter()
    graph = graphgen.generate(cfg, int(cfg["graph_seed"]))
    phase("graph", t)
    log(f"graph: {graph.num_nodes} nodes, {graph.num_edges} directed edges, "
        f"{graph.train_ids.size} training vertices")
    t = time.perf_counter()
    tr = make_trainer(cfg, cell, graph, trace)
    phase("trainer (presample, partition, init)", t)
    log(f"setup: presample {tr.t_presample!r} s, partition {tr.t_partition!r} s")
    t = time.perf_counter()
    params0, recorder = start(tr, cfg, cell, seed)
    phase("weights", t)
    t = time.perf_counter()
    prog = first_steps(tr, cfg, params0)
    phase(f"first {CHECK_STEPS} steps", t)
    t = time.perf_counter()
    programs = StepPrograms(tr) if trace else None
    warm_epoch = program_attr(tr, "_epoch")
    ep = tr.train_epoch()
    phase(f"warm-up (1 epoch, recompiles {int(ep.recompiles.get('misses', 0))})", t)
    setup_s = time.perf_counter() - t_start
    log(f"setup: total {setup_s!r} s")

    # ---- the window ---------------------------------------------------- #
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    epochs, window_epochs = [], []
    profiler = jax.profiler.trace(trace_dir) if trace else None
    if profiler is not None:
        profiler.__enter__()
    try:
        annotation = jax.profiler.TraceAnnotation(WINDOW_MARK)
        annotation.__enter__()
        if programs is not None:
            programs.in_window = True
        t0, c0 = time.perf_counter(), time.process_time()
        while True:
            # the window replays the warm-up epoch: its batches are padded
            # to shapes that are compiled already, so nothing compiles
            # inside it, and a seed fixes the window's work
            set_program_attr(tr, "_epoch", warm_epoch)
            window_epochs.append(warm_epoch)
            epochs.append(tr.train_epoch())
            if time.perf_counter() - t0 >= seconds:
                break
        t1, c1 = time.perf_counter(), time.process_time()
        if programs is not None:
            programs.in_window = False
        annotation.__exit__(None, None, None)
    finally:
        if profiler is not None:
            profiler.__exit__(None, None, None)
    iters = [it for ep in epochs for it in ep.iters]
    window_s = t1 - t0
    targets = len(iters) * int(cell["batch_size"])
    misses = sum(int(ep.recompiles.get("misses", 0)) for ep in epochs)
    losses = np.array([it.loss for it in iters])
    log(f"window: {len(epochs)} epochs, {len(iters)} steps, {window_s!r} s, "
        f"recompiles {misses}, loss {losses[0]!r} -> {losses[-1]!r}")

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": int(stats.get("peak_bytes_in_use", 0))}

    metrics, breakdown = {}, None
    e2e, layer = declared(workload, bench) if bench else ([], [])
    if not trace:
        values = {
            "train_targets_per_s": targets / window_s,
            "host_cpu_ms_per_target": (c1 - c0) * 1e3 / targets,
            "setup_s": setup_s,
        }
        for m in e2e:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        from bench import trace_reduce

        ctx = window_context(
            tr, cfg, recorder, window_epochs, iters, t0, t1,
            trace_reduce.load(trace_dir, (WINDOW_MARK,)), peaks, programs.compiled(),
        )
        shutil.rmtree(trace_dir, ignore_errors=True)
        log(f"trace: busy {ctx['busy_s']!r} s, of it with no scope {ctx['unscoped_s']!r} s")
        device["busy_s"] = ctx["busy_s"]
        device["window_s"] = ctx["trace_window_s"]
        for m in layer:
            value = load_metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        breakdown = ctx["breakdown"]

    # ---- the check ----------------------------------------------------- #
    del tr, epochs
    gc.collect()
    t = time.perf_counter()
    blocks = [recorder.blocks[i] for i in range(CHECK_STEPS)]
    ref = reference.run_steps(cfg, params0, blocks, graph, cell["fanouts"],
                              int(cell["batch_size"]),
                              precision=cfg["matmul_precision"])
    nums = correct.numbers(prog, ref, params0)
    limits = cell["correct_limits"]
    ok = correct.judge(nums, limits) and bool(np.isfinite(losses).all())
    log(f"check: reference {time.perf_counter() - t!r} s; program losses "
        f"{prog['losses']}, reference {ref['losses']}")
    checks = {k: {"value": nums[k], "limit": limits.get(k)} for k in correct.NUMBERS}
    checks["window_losses_finite"] = {"value": int(np.isfinite(losses).all()), "limit": 1}
    result = {
        "correct": ok,
        "attempted": len(iters),
        "failed": int((~np.isfinite(losses)).sum()),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def device_peaks(kind: str) -> dict:
    """The published peaks of one chip of ``kind``; an unknown kind is an
    error, never a default."""
    peaks = load_json(BENCH / "peaks.json")
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return peaks[kind]


def window_context(tr, cfg, recorder, window_epochs, iters, t0, t1,
                   trace, peaks, programs) -> dict:
    """What the per-layer metric readers read: the window's spans, the
    required work of its blocks, the reduced device trace, the peaks, and
    ``scope_seconds(scope)``: the window's device seconds of ops under a
    named scope (``programs``: ``StepPrograms.compiled``)."""
    from bench import counts, trace_reduce

    chrome = tr.obs.tracer.to_chrome()
    origin = tr.obs.tracer.t_origin
    main = threading.get_ident()
    spans = [
        {"name": ev["name"], "t0": origin + ev["ts"] / 1e6,
         "t1": origin + (ev["ts"] + ev["dur"]) / 1e6, "main": ev["tid"] == main,
         "args": ev.get("args", {})}
        for ev in chrome["traceEvents"] if ev.get("ph") == "X"
    ]
    sizes = [s for e in window_epochs
             for (e2, _), s in sorted(recorder.sizes.items()) if e2 == e]
    mark = trace["marks"].get(WINDOW_MARK)
    if mark is None:
        raise RuntimeError("the trace holds no window annotation")
    # the annotation opens just before t0 and closes just after t1
    offset_ns = mark[0] - int(t0 * 1e9)
    w0, w1 = mark
    busy = trace_reduce.busy_seconds(trace, w0, w1)
    ops = trace_reduce.op_seconds(trace, w0, w1)
    gaps = trace_reduce.idle_gaps(trace, w0, w1)[:10]
    paths = trace_reduce.op_paths(
        trace, [(name, trace_reduce.hlo_scopes(text)) for name, text in programs])
    return {
        "steps": len(iters), "window_s": t1 - t0,
        "spans": [s for s in spans if s["t1"] > t0 and s["t0"] < t1],
        "t0": t0, "t1": t1,
        "flops": [counts.step_flops(cfg, s) for s in sizes],
        "trace": trace, "trace_t0": w0, "trace_t1": w1,
        "busy_s": busy, "trace_window_s": (w1 - w0) / 1e9,
        "peaks": peaks, "hlo": [text for _, text in programs],
        "scope_seconds": lambda scope: trace_reduce.scope_seconds(trace, paths, w0, w1, scope),
        "unscoped_s": trace_reduce.scope_seconds(trace, paths, w0, w1, None),
        "breakdown": {
            "device_ops": [[n, s] for n, s in
                           sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": [[gap_label(spans, (a - offset_ns) / 1e9, (b - offset_ns) / 1e9),
                           (b - a) / 1e9] for a, b in gaps],
        },
    }


def gap_label(spans: list, a: float, b: float) -> str:
    """What the training loop's thread was doing over most of ``[a, b]``."""
    best, key = "between steps", (0.0, 0.0)
    for s in spans:
        overlap = min(b, s["t1"]) - max(a, s["t0"])
        # the innermost span of a step wins a tie: it is the shorter
        if (s["main"] and s["name"].startswith("step") and overlap > 0
                and (overlap, s["t0"] - s["t1"]) > key):
            best, key = s["name"], (overlap, s["t0"] - s["t1"])
    return f"host: {best}"
