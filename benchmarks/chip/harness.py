"""One run of one cell: set-up, the measured window, the check, the result.

The cell (``BENCHMARK.json`` → ``workloads``) names a configuration file
(``configs/<name>.json``, whose ``family`` names the module that knows the
program's stages and the plain reference) and a traffic file
(``traffic/<name>.json``).  Each metric is read by ``metrics/<name>.py``.
Nothing here names a cell, a configuration or a metric.

The window drives the program as a server would: ``Orchestrator`` plans the
one chip, and at each wake-up (an arrival, or an image delivered) the
pending set goes to ``Dispatcher.dispatch``; each decision's request runs
E -> D -> C through the program's stage functions, compiled ahead of time
for each shape in set-up.  The loop holds no policy of its own: it admits
arrivals, calls the dispatcher, launches what it grants, and blocks only on
the image's copy to the host, which is what a user receives.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import importlib.util
import json
import math
import pathlib
import shutil
import tempfile
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


# --- the cell ---------------------------------------------------------------

@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list


def load_cell(name: str, spec_path=None) -> Cell:
    spec = json.loads(pathlib.Path(spec_path or ROOT / "BENCHMARK.json").read_text())
    wl = next((w for w in spec["workloads"] if w["name"] == name), None)
    if wl is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cfg_entry = next(c for c in spec["configs"] if c["name"] == wl["config"])
    config = json.loads((ROOT / cfg_entry["file"]).read_text())
    from benchmarks.chip import traffic as tr
    mine = lambda m: "workloads" not in m or name in m["workloads"]
    return Cell(name=name, chips=wl["chips"], config=config,
                traffic=tr.load(wl["traffic"]),
                end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                per_layer=[m for m in spec["per_layer"] if mine(m)])


def family(config: dict):
    mod = importlib.import_module(f"benchmarks.chip.configs.{config['family']}")
    return mod.Family(config)


def reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise NoChip(f"device kind {kind!r} is not in peaks.json")
    return table["devices"][kind]


# --- set-up -----------------------------------------------------------------

def enable_compile_cache():
    """JAX's persistent cache at a fixed path inside the checkout; every
    program is cached, however short its compile."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def make_weights(fam, shapes, seed: int):
    """Every weight leaf drawn on the device in one jitted call from the
    seed: uniform, of the leaf's standard deviation, in the leaf's type."""
    import jax

    flat, tree = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    for path, x in flat:
        names = tuple(getattr(k, "key", getattr(k, "idx", None)) for k in path)
        bound = fam.weight_std(names, tuple(x.shape)) * math.sqrt(3.0)
        leaves.append((tuple(x.shape), x.dtype, bound))
    words = np.random.default_rng([seed, 1]).integers(0, 2**32, 4, dtype=np.uint32)

    @jax.jit
    def init(data):
        key = jax.random.wrap_key_data(data, impl="rbg")
        return jax.tree_util.tree_unflatten(tree, [
            jax.random.uniform(jax.random.fold_in(key, i), s, d, -b, b)
            for i, (s, d, b) in enumerate(leaves)])

    t = time.perf_counter()
    exe = init.lower(words).compile()
    t_compile = time.perf_counter() - t
    return exe(words), t_compile


def request_inputs(fam, seed: int, n: int, vocab: int, dev):
    """Prompt tokens and the noise key of each request, on the device."""
    import jax
    rng = np.random.default_rng([seed, 2])
    toks = rng.integers(0, vocab, (n, 1, fam.cond_len), dtype=np.int32)
    keys = np.random.default_rng([seed, 3]).integers(0, 2**32, (n, 2), dtype=np.uint32)
    return ([jax.device_put(t, dev) for t in toks],
            [jax.device_put(k, dev) for k in keys])


class Stages:
    """The program's stage functions compiled once per shape, under the
    names ``Family.stage_fns`` gives them."""

    def __init__(self, fam, pcfg):
        self.fam, self.pcfg = fam, pcfg
        self.exe = {}
        self.compile_s = {}

    def run(self, params, res, tokens, key):
        import jax
        fns = self.fam.stage_fns(self.pcfg, res)
        out = {}
        args = {"E": lambda: (params["encode"], tokens),
                "D": lambda: (params["diffuse"], out["cond"], key),
                "C": lambda: (params["decode"], out["latents"])}
        for stage, slot in (("E", "cond"), ("D", "latents"), ("C", "image")):
            name, fn = fns[stage]
            a = args[stage]()
            exe = self.exe.get(name)
            if exe is None:
                fn.__name__ = fn.__qualname__ = name
                t = time.perf_counter()
                exe = self.exe[name] = jax.jit(fn).lower(*a).compile()
                self.compile_s[name] = time.perf_counter() - t
            out[slot] = exe(*a)
        return out


# --- the window -------------------------------------------------------------

class Spans:
    """Host spans of the harness, kept in memory; written into the
    profiler's trace as well while one is recorded."""

    def __init__(self):
        self.rec = []
        self.tracing = False

    @contextlib.contextmanager
    def __call__(self, name):
        import jax
        ann = jax.profiler.TraceAnnotation(name) if self.tracing else None
        if ann:
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.rec.append((name, t0, time.perf_counter()))
            if ann:
                ann.__exit__(None, None, None)

    def durations(self, name):
        return [t1 - t0 for n, t0, t1 in self.rec if n == name]


@dataclasses.dataclass
class Record:
    """What one run measured: the metrics' readers take this."""
    cell: Cell
    fam: object
    seconds: float
    setup_s: float
    requests: list            # dicts: due, deadline, res, admit, launch, done
    spans: Spans
    device: dict
    peaks: dict
    memory: dict
    param_shapes: dict
    trace: object = None      # trace.Summary in a traced run

    def done_in(self, t_end):
        return [r for r in self.requests if r["done"] is not None
                and r["done"] <= t_end]


def serve_window(cell, fam, pcfg, stages, params, inputs, seconds, traced,
                 spans, t_origin):
    """Offer the cell's traffic for ``seconds``; returns the requests."""
    import jax

    from benchmarks.chip import trace as trace_lib
    from benchmarks.chip import traffic as tr
    from repro.core.dispatcher import Dispatcher
    from repro.core.orchestrator import Orchestrator
    from repro.core.profiler import Profiler
    from repro.core.request import Request

    traffic = cell.traffic
    arrivals = tr.schedule(traffic, seconds)
    follow_all = traffic["at_window_end"] == "follow_to_completion"
    limit = seconds + traffic["drain_s"] if follow_all else seconds
    prof = Profiler(pcfg)
    orch = Orchestrator(prof, num_chips=cell.chips)
    mk = lambda a: Request(pcfg.name, a.res, arrival=a.due,
                           deadline=a.due + a.deadline_s, cond_len=fam.cond_len)
    plan = orch.generate([mk(a) for a in arrivals])
    if plan is None:
        raise RuntimeError("the orchestrator found no placement on "
                           f"{cell.chips} chip(s)")
    disp = Dispatcher(prof)
    idle = set(range(plan.num_units))
    # the profiler's per-class tables, as a warmed-up server has them
    disp.dispatch([mk(a) for a in arrivals[:len(traffic["classes"]) * 2]],
                  plan, idle, {g: 0.0 for g in idle}, 0.0)
    disp = Dispatcher(prof)
    reqs = [mk(a) for a in arrivals]
    recs = {r.rid: {"due": a.due, "deadline": a.due + a.deadline_s,
                    "res": a.res, "index": a.index, "admit": None,
                    "launch": None, "done": None, "after_wait": False}
            for r, a in zip(reqs, arrivals)}
    outputs = {}
    trace_len = traffic["trace_seconds"]
    trace_at = seconds - traffic["trace_margin_s"] - trace_len
    trace_dir, window_ann = None, None
    woke_from_wait = False
    clock = time.perf_counter
    t0 = clock()
    setup_s = t0 - t_origin
    pending, nxt = [], 0

    def now():
        return clock() - t0

    def trace_tick(t):
        """Start or stop the traced sub-window when its time has come."""
        nonlocal trace_dir, window_ann
        if traced and trace_dir is None and t >= trace_at:
            trace_dir = tempfile.mkdtemp(prefix="chip_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # spans only, no call tracing
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            spans.tracing = True
            window_ann = jax.profiler.TraceAnnotation(trace_lib.WINDOW_SPAN)
            window_ann.__enter__()
        elif window_ann is not None and t >= trace_at + trace_len:
            window_ann.__exit__(None, None, None)
            window_ann = None
            spans.tracing = False

    def wait_until(t):
        with spans("wait_arrival"):
            while True:
                trace_tick(now())
                dt = t - now()
                if dt <= 0:
                    return
                time.sleep(min(dt, 0.01))

    while True:
        t = now()
        trace_tick(t)
        while nxt < len(reqs) and arrivals[nxt].due <= t:
            r = reqs[nxt]
            recs[r.rid]["admit"] = t
            recs[r.rid]["after_wait"] = woke_from_wait
            pending.append(r)
            nxt += 1
        woke_from_wait = False
        if t >= limit or (nxt >= len(reqs) and not pending):
            break
        if not pending:
            wait_until(min(arrivals[nxt].due, limit))
            woke_from_wait = True
            continue
        with spans("dispatch"):
            decisions = disp.dispatch(pending, plan, idle,
                                      {g: t for g in idle}, t)
        if not decisions:
            if nxt >= len(reqs):
                break
            wait_until(min(arrivals[nxt].due, limit))
            woke_from_wait = True
            continue
        launched = []
        for d in decisions:
            if d.corequests:
                raise NotImplementedError(
                    "the dispatcher batched requests; the harness serves "
                    "batches of one")
            r = d.request
            pending.remove(r)
            i = recs[r.rid]["index"]
            with spans("launch"):
                recs[r.rid]["launch"] = now()
                out = stages.run(params, r.resolution, inputs[0][i], inputs[1][i])
            launched.append((r, out))
        for r, out in launched:
            with spans("copy"):
                image = np.asarray(out["image"])
            recs[r.rid]["done"] = now()
            out["image"] = image
            outputs[r.rid] = out
    if window_ann is not None:
        window_ann.__exit__(None, None, None)
        spans.tracing = False
    if trace_dir is not None:
        # stopped only now: writing the trace out stalls the host for
        # seconds, which would hold up requests still being served
        jax.profiler.stop_trace()
    sent = [recs[r.rid] | {"rid": r.rid} for r in reqs
            if recs[r.rid]["admit"] is not None]
    return sent, outputs, setup_s, trace_dir


# --- the run ----------------------------------------------------------------

@dataclasses.dataclass
class Setup:
    """What a run builds before its window: the program's configuration,
    the weights on the device and the stage programs, warmed up."""
    fam: object
    pcfg: object
    shapes: dict
    params: dict
    stages: Stages
    devices: list
    log: list


def check_chip(chips: int, require_chip: bool = True):
    import jax
    devices = jax.devices()
    if require_chip and (devices[0].platform != "tpu" or len(devices) < chips):
        raise NoChip(f"JAX found {len(devices)} {devices[0].platform} device(s); "
                     f"the cell needs {chips} TPU chip(s)")
    return devices


def prepare(cell: Cell, seed: int, devices, t_origin: float) -> Setup:
    """Weights from the seed, and each of the cell's shapes compiled (or
    loaded from the cache) and run once."""
    import jax

    from benchmarks.chip import traffic as tr

    enable_compile_cache()
    log = [f"set-up: chip ready {time.perf_counter() - t_origin:.3f} s "
           "after the process started"]
    fam = family(cell.config)
    pcfg = fam.program_config()
    shapes = fam.param_shapes(pcfg)
    t = time.perf_counter()
    params, t_compile = make_weights(fam, shapes, seed)
    jax.block_until_ready(params)
    log.append(f"set-up: weights {time.perf_counter() - t:.3f} s "
               f"(of which compile or cache load {t_compile:.3f} s)")
    stages = Stages(fam, pcfg)
    tok, key = request_inputs(fam, seed + 1, 1, cell.config["encoder_vocab"],
                              devices[0])
    for res in tr.resolutions(cell.traffic):
        t = time.perf_counter()
        np.asarray(stages.run(params, res, tok[0], key[0])["image"])
        log.append(f"set-up: warm-up at {res} px {time.perf_counter() - t:.3f} s")
    log.append("set-up: compile or cache load " + ", ".join(
        f"{k} {v:.3f} s" for k, v in stages.compile_s.items()))
    return Setup(fam, pcfg, shapes, params, stages, devices, log)


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_origin: float, require_chip: bool = True, control=False,
             peaks=None):
    """One run.  Returns (result dict, lines for standard error, the
    control's verdict and readings, or None).

    ``require_chip=False`` and ``peaks`` let the tests rehearse a run on
    the CPU; ``control`` also judges the family's control, put in the
    program's place for the same sampled requests."""
    import jax

    devices = check_chip(cell.chips, require_chip)
    dev = devices[0]
    peaks = peaks or peaks_for(dev.device_kind)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append(ev)
        if "backend_compile" in ev else None)
    setup = prepare(cell, seed, devices, t_origin)
    return measure(cell, setup, seed, seconds, traced, t_origin, control,
                   peaks, compiles)


def judge(checks: dict, limits: dict, n_items: int) -> bool:
    """``correct``: something was checked, and every number is within
    its limit."""
    return n_items > 0 and all(checks[k] <= limits[k] for k in limits)


def measure(cell, setup, seed, seconds, traced, t_origin, control=False,
            peaks=None, compiles=()):
    """The window, the metrics and the check, on a prepared set-up."""
    import jax

    from benchmarks.chip import trace as trace_lib
    from benchmarks.chip import traffic as tr

    fam, dev = setup.fam, setup.devices[0]
    log = list(setup.log)
    t = time.perf_counter()
    n_max = len(tr.schedule(cell.traffic, seconds))
    inputs = request_inputs(fam, seed, n_max, cell.config["encoder_vocab"], dev)
    log.append(f"set-up: inputs of {n_max} requests {time.perf_counter() - t:.3f} s")
    spans = Spans()
    n_before = len(compiles)
    t_plan = time.perf_counter()
    pauses = []
    gc.callbacks.append(_gc_timer(pauses))
    try:
        sent, outputs, setup_s, trace_dir = serve_window(
            cell, fam, setup.pcfg, setup.stages, setup.params, inputs,
            seconds, traced, spans, t_origin)
    finally:
        gc.callbacks.pop()
    log.append(f"set-up: planning {t_origin + setup_s - t_plan:.3f} s; "
               f"the window opened {setup_s:.3f} s after the process started")
    in_window = len(compiles) - n_before

    stats = dev.memory_stats() or {}
    memory = {k: stats.get(k) for k in ("peak_bytes_in_use", "peak_bytes_reserved",
                                        "bytes_limit")}
    peak = (memory["peak_bytes_in_use"] or 0) + (memory["peak_bytes_reserved"] or 0)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(setup.devices), "memory_peak_bytes": int(peak)}

    summary = None
    if trace_dir is not None:
        files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        summary = trace_lib.reduce(files[-1])
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    rec = Record(cell=cell, fam=fam, seconds=seconds, setup_s=setup_s,
                 requests=sent, spans=spans, device=device, peaks=peaks,
                 memory=memory, param_shapes=setup.shapes, trace=summary)
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = reader(m["name"])(rec)
        if v is None:
            if not traced:
                raise RuntimeError(f"end-to-end metric {m['name']} read nothing")
            continue
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    # the check: a sample of the finished requests, the longest among them
    done = [r for r in sent if r["done"] is not None]
    follow = cell.traffic["at_window_end"] == "follow_to_completion"
    failed = sum(1 for r in sent if r["done"] is None) if follow else 0
    sample = _sample(done, cell.traffic["check_sample"], seed)
    items = [{"res": r["res"], "tokens": inputs[0][r["index"]],
              "key": inputs[1][r["index"]], **outputs[r["rid"]]} for r in sample]
    del outputs
    gc.collect()
    limits = cell.config["check_limits"]
    t_check = time.perf_counter()
    checks = fam.check(setup.params, items)
    t_check = time.perf_counter() - t_check
    correct = judge(checks, limits, len(items))
    verdict = None
    if control:
        c = fam.check(setup.params, items, control=True)
        verdict = {"correct": judge(c, limits, len(items)), "checks": c}

    lates = [1e3 * (r["admit"] - r["due"]) for r in sent if r["after_wait"]]
    imgs = [np.asarray(it["image"]) for it in items]
    log += [
        f"window: {seconds} s, sent {len(sent)}, delivered in window "
        f"{len(rec.done_in(seconds))}, delivered by the end {len(done)}, "
        f"failed {failed}, still queued {len(sent) - len(done)}",
        f"window: compilations inside the window {in_window}",
        f"generator: late after a wait, mean "
        f"{np.mean(lates) if lates else 0.0:.3f} ms, max "
        f"{max(lates) if lates else 0.0:.3f} ms over {len(lates)} arrivals",
        *host_report(spans, sent, pauses, t_origin + setup_s),
        f"memory: {json.dumps(memory)}",
        f"check: reference over {len(items)} requests {t_check:.3f} s",
        "check: sampled " + ", ".join(
            f"{it['res']} px (image std {im.std():.3f}, "
            f"|x|>0.99 {np.mean(np.abs(im) > 0.99):.3f})"
            for it, im in zip(items, imgs)),
    ]
    if verdict is not None:
        log.append(f"control: {json.dumps(verdict)}")
    result = {"correct": correct, "attempted": len(sent), "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        top = sorted(summary.ops.items(), key=lambda kv: (-kv[1], kv[0]))[:10]
        result["breakdown"] = {
            "device_ops": [list(x) for x in top],
            "idle_gaps": [list(x) for x in summary.gap_totals()[:10]]}
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]}
                        for k in limits}
    for k in limits:
        log.append(f"check {k}: {checks[k]!r} limit {limits[k]!r}")
    return result, log, verdict


def _gc_timer(pauses):
    """A ``gc.callbacks`` entry that appends (start, seconds, generation)
    of each collection to ``pauses``."""
    start = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            start[0] = time.perf_counter()
        else:
            pauses.append((start[0], time.perf_counter() - start[0],
                           info["generation"]))
    return on_gc


def host_report(spans, sent, pauses, t0):
    """Lines on where a slow request's time went: the longest host span of
    each kind, the longest stretch the loop spent outside every span, the
    garbage collector's pauses, and each class's launch -> image time.
    Times ``at`` are seconds into the window."""
    out = []
    longest = {}
    for name, a, b in spans.rec:
        if b - a > longest.get(name, (0.0, 0.0))[0]:
            longest[name] = (b - a, a - t0)
    out.append("host: longest span " + ", ".join(
        f"{n} {d:.4f} s at {at:.2f}" for n, (d, at) in sorted(longest.items())))
    gaps = [(b[1] - a[2], a[2] - t0) for a, b in zip(spans.rec, spans.rec[1:])]
    gap = max(gaps, default=(0.0, 0.0))
    out.append(f"host: longest time outside every span {gap[0]:.4f} s at {gap[1]:.2f}")
    pauses = [p for p in pauses if p[0] >= t0]
    slow = max(pauses, key=lambda p: p[1], default=(t0, 0.0, 0))
    out.append(f"host: gc in the window {len(pauses)} collections, "
               f"{sum(p[1] for p in pauses):.4f} s in all, longest "
               f"{slow[1]:.4f} s (generation {slow[2]}) at {slow[0] - t0:.2f}")
    for res in sorted({r["res"] for r in sent}):
        serve = [(r["done"] - r["launch"], r["launch"]) for r in sent
                 if r["res"] == res and r["done"] is not None]
        if serve:
            worst = max(serve)
            out.append(f"service: {res} px launch -> image median "
                       f"{np.median([s for s, _ in serve]):.4f} s, longest "
                       f"{worst[0]:.4f} s launched at {worst[1]:.2f}")
    return out


def _sample(done, n, seed):
    """``n`` finished requests drawn from the seed, the longest among them."""
    if not done:
        return []
    rng = np.random.default_rng([seed, 4])
    top = max(r["res"] for r in done)
    longest = [r for r in done if r["res"] == top]
    first = longest[int(rng.integers(len(longest)))]
    rest = [r for r in done if r is not first]
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False) \
        if rest and n > 1 else []
    return [first] + [rest[int(i)] for i in sorted(pick)]
