"""Serve sd3 at published widths on one TPU chip, end to end.

  python chip_smoke.py                # one chip: plan, dispatch, E -> D -> C
  python chip_smoke.py --chips 4      # Ulysses attention on a 4-chip mesh

One chip (the default): the published sd3 pipeline (T5-XXL-width encoder,
24-layer 1536-wide DiT, AE-KL decoder, 20 steps) with seeded random
weights is planned by ``Orchestrator``, dispatched by ``Dispatcher`` and
served stage by stage, with each stage a ``jax.jit`` program compiled once
per shape and the condition and latents handed between stages as device
arrays.  One DiT forward on the chip is checked against the same forward on
the host CPU.  The times printed are smoke timings, not benchmark results.

``--chips 4``: ``ulysses_attention`` over meshes of 2 and 4 chips on one
1024 px request's joint sequence (4173 positions, 24 heads of 64), checked
against one-chip attention.  No other phase runs.

The script holds the chip in this one process and starts no child.  Without
a TPU it exits non-zero and prints no result.  The last line of standard
output is one JSON object: ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))

RESOLUTIONS = (512, 1024, 512, 1024)   # request order, served through plan
COND_LEN = 77
# Chip-vs-CPU tolerance for one DiT forward, as relative Frobenius error.
# Both sides run the same bf16 weights and inputs with f32 accumulation; they
# differ only in the order of reductions and so in bf16 rounding, which the
# 24 blocks compound.  At 24 layers (width 256, on the CPU) bf16 differs from
# an f32 evaluation by 1.1e-2 relative, so two bf16 evaluations should agree
# well inside 5e-2; a wrong layout, mask or kernel gives errors of order 1.
DIT_REL_TOL = 5e-2
# Ulysses vs one-chip attention: the same bf16 math, padded keys masked to
# exact zeros; only the softmax sum's reduction order differs.
ULYSSES_REL_TOL = 1e-2


def _keep_cpu_backend():
    """The chip-vs-CPU check needs the host CPU as a second backend."""
    plats = os.environ.get("JAX_PLATFORMS", "")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"


def _rel(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _versions() -> dict:
    import importlib.metadata as md

    import jax
    import jaxlib
    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = "not installed"
    return {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
            "libtpu": libtpu}


def init_dit_modulated(dit, key, kmod):
    """``diffusion.init`` with the AdaLN modulation drawn from ``kmod``.

    ``diffusion.init`` zeroes ``mod`` and ``final_mod`` (AdaLN-Zero), which
    makes every DiT block the identity; a finite output would then prove
    little.  Here they are drawn like the other projections."""
    import jax

    from repro.models import common, diffusion

    d = dit.d_model
    p = diffusion.init(dit, key)
    lk = jax.random.split(kmod, dit.num_layers + 1)
    mod = jax.vmap(lambda k: common.dense_init(k, (d, 6 * d), dit.dtype))(lk[1:])
    p["layers"] = dict(p["layers"], mod=mod)
    p["final_mod"] = common.dense_init(lk[0], (d, 2 * d), dit.dtype)
    return p


def init_params(cfg, key):
    """Seeded weights for the three stages, drawn on the device by jitted
    programs: 10.8 GiB of bf16 is never made on the host and copied over."""
    import jax

    from repro.models import diffusion, transformer

    def init_encoder(k):  # the E stage reads no LM head: never make one
        p = transformer.init(cfg.encoder, k)
        p.pop("lm_head", None)
        return p

    ke, kd, kc, km = jax.random.split(key, 4)
    return {
        "encode": jax.jit(init_encoder)(ke),
        "diffuse": jax.jit(functools.partial(init_dit_modulated, cfg.dit))(kd, km),
        "decode": jax.jit(functools.partial(diffusion.init_decoder, cfg.decoder))(kc),
    }


class StagePrograms:
    """``jax.jit`` stage programs, compiled ahead of time once per shape;
    compile time is kept apart as set-up."""

    def __init__(self, cfg):
        import jax

        from repro.models import pipeline as pl
        self._jit = {
            "E": jax.jit(functools.partial(pl.encode, cfg)),
            "D": jax.jit(functools.partial(pl.diffuse, cfg), static_argnums=(2,)),
            "C": jax.jit(functools.partial(pl.decode, cfg), static_argnums=(2,)),
        }
        self._compiled = {}
        self.setup_lines = []

    def ready(self, stage, key, *args):
        """Compile ``stage`` for the shape class ``key`` (once, timed as
        set-up) and return a thunk that runs it on ``args``.  Tuple
        arguments are static shapes, folded into the compiled program."""
        if (stage, key) not in self._compiled:
            t0 = time.perf_counter()
            exe = self._jit[stage].lower(*args).compile()
            self.setup_lines.append(
                f"set-up: compile {stage}@{key} {time.perf_counter() - t0:.2f} s "
                f"(compiled temporaries "
                f"{exe.memory_analysis().temp_size_in_bytes / 2**30:.3f} GiB)")
            self._compiled[(stage, key)] = exe
        exe = self._compiled[(stage, key)]
        dyn = [a for a in args if not isinstance(a, tuple)]
        return lambda: exe(*dyn)


def serve(seed: int) -> None:
    """Plan, dispatch and serve sd3 requests on the chip."""
    import jax
    import numpy as np

    import repro.configs as C
    from repro.core.dispatcher import Dispatcher
    from repro.core.orchestrator import Orchestrator
    from repro.core.profiler import Profiler
    from repro.core.request import Request

    cfg = C.get("sd3")
    dev = jax.devices()[0]
    print(f"config: sd3 encoder {cfg.encoder.num_layers}x{cfg.encoder.d_model} "
          f"(ff {cfg.encoder.d_ff}), DiT {cfg.dit.num_layers}x{cfg.dit.d_model} "
          f"({cfg.dit.num_heads} heads), decoder base {cfg.decoder.base_channels}, "
          f"{cfg.num_steps} steps", flush=True)

    prof = Profiler(cfg)
    orch = Orchestrator(prof, num_chips=len(jax.devices()))
    pending = []
    for res in RESOLUTIONS:
        r = Request("sd3", res, cond_len=COND_LEN)
        r.deadline = 2.5 * prof.pipeline_time(r)
        pending.append(r)
    plan = orch.generate(pending)
    print(f"placement plan ({len(jax.devices())} chip): {plan.type_histogram()}",
          flush=True)
    disp = Dispatcher(prof)

    t0 = time.perf_counter()
    key = jax.random.PRNGKey(seed)
    params = init_params(cfg, key)
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    rng = np.random.default_rng(seed)
    progs = StagePrograms(cfg)
    cond_512 = None

    def timed(times, stage, key, *args):
        run = progs.ready(stage, key, *args)
        t = time.perf_counter()
        out = run()
        out.block_until_ready()
        times[stage] = time.perf_counter() - t
        return out

    while pending:
        idle = set(range(plan.num_units))
        decisions = disp.dispatch(pending, plan, idle, {g: 0.0 for g in idle}, 0.0)
        if not decisions:
            raise RuntimeError(f"dispatcher granted none of {len(pending)} requests")
        for d in decisions:
            req = d.request
            pending.remove(req)
            grid = cfg.latent_grid(req.resolution)
            lat_shape = (1, cfg.latent_tokens(req.resolution), cfg.dit.latent_dim)
            tokens = jax.device_put(
                rng.integers(0, cfg.encoder.vocab_size, (1, req.cond_len),
                             dtype=np.int32), dev)
            dkey = jax.random.fold_in(key, req.rid)
            times = {}
            cond = timed(times, "E", req.cond_len, params, tokens)
            lat = timed(times, "D", req.resolution, params, cond, lat_shape, dkey)
            img = timed(times, "C", req.resolution, params, lat, grid)
            out = np.asarray(img)
            want = (1, req.resolution, req.resolution, 3)
            if out.shape != want:
                raise AssertionError(f"output shape {out.shape} != {want}")
            if not np.isfinite(out).all():
                raise AssertionError(f"non-finite output at {req.resolution} px")
            if out.min() < -1.0 or out.max() > 1.0:
                raise AssertionError(f"C output outside [-1, 1]: "
                                     f"[{out.min()}, {out.max()}]")
            if not np.isfinite(np.asarray(lat)).all():
                raise AssertionError(f"non-finite latents at {req.resolution} px")
            print(f"request {req.rid} res={req.resolution}: VR V{d.vr_type} "
                  f"D on units {d.d_units} (degree {d.degree}), E on {d.e_units}, "
                  f"C on {d.c_units}; output {out.shape} "
                  f"range [{out.min():.4f}, {out.max():.4f}]; smoke timing "
                  f"(not a benchmark result) E {times['E']:.4f} s, "
                  f"D {times['D']:.4f} s, C {times['C']:.4f} s", flush=True)
            if req.resolution == 512:
                cond_512 = cond
    for line in progs.setup_lines:
        print(line, flush=True)
    print(f"set-up: weights init {init_s:.2f} s", flush=True)

    check_dit_against_cpu(cfg, params["diffuse"], cond_512, seed)
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    print(f"peak_bytes_in_use: {peak} ({peak / 2**30:.3f} GiB)" if peak else
          "peak_bytes_in_use: not reported by this backend", flush=True)
    print(f"memory_stats: {json.dumps(stats, sort_keys=True)}", flush=True)


def check_dit_against_cpu(cfg, dit_params, cond, seed: int) -> None:
    """One DiT forward at 512 px on the chip vs the same on the host CPU."""
    import jax
    import jax.numpy as jnp

    from repro.models import diffusion

    lx = cfg.latent_tokens(512)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (1, lx, cfg.dit.latent_dim),
                          jnp.float32)
    t = jnp.full((1,), 500.0, jnp.float32)
    fwd = jax.jit(functools.partial(diffusion.forward, cfg.dit))
    on_chip = fwd(dit_params, x, t, cond)
    on_chip.block_until_ready()
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    on_cpu = fwd(*jax.device_put((dit_params, x, t, cond), cpu))
    on_cpu.block_until_ready()
    rel = _rel(on_chip, on_cpu)
    print(f"DiT forward at 512 px, chip vs CPU: relative error {rel:.3e} "
          f"(tolerance {DIT_REL_TOL:.0e}; CPU run {time.perf_counter() - t0:.1f} s)",
          flush=True)
    if not rel <= DIT_REL_TOL:
        raise AssertionError(f"chip DiT forward differs from CPU: {rel:.3e}")


def check_ulysses(devices, seq_len: int, heads: int, head_dim: int,
                  degrees, seed: int) -> None:
    """``ulysses_attention`` at each SP degree vs one-device attention."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.models import common
    from repro.sharding import sequence_parallel as sp

    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (1, seq_len, heads, head_dim)
    q, k, v = (jax.device_put(jax.random.normal(kk, shape, jnp.bfloat16), devices[0])
               for kk in ks)
    ref = jax.jit(lambda q, k, v: common.attention(q, k, v, None))(q, k, v)
    ref.block_until_ready()
    for n in degrees:
        mesh = make_mesh((n,), ("model",), devices=devices[:n])
        # 4173 rows cannot be split evenly over the mesh, so the arrays are
        # replicated; inside, ulysses pads and shards the sequence itself
        rep = NamedSharding(mesh, P())
        args = jax.device_put((q, k, v), rep)
        exe = jax.jit(functools.partial(sp.ulysses_attention, mesh=mesh)
                      ).lower(*args).compile()
        # the head/sequence re-shards are the cross-chip work; without them
        # the whole sequence ran on each chip
        if "all-to-all" not in exe.as_text():
            raise AssertionError(f"degree {n}: no all-to-all in the program")
        out = exe(*args)
        out.block_until_ready()
        on = out.sharding.device_set
        rel = _rel(out, ref)
        temp = exe.memory_analysis().temp_size_in_bytes
        print(f"ulysses degree {n}: L={seq_len} (pad {(-seq_len) % n}), "
              f"relative error vs one chip {rel:.3e} (tolerance "
              f"{ULYSSES_REL_TOL:.0e}); output on devices "
              f"{sorted(d.id for d in on)}; compiled temporaries per device "
              f"{temp / 2**30:.3f} GiB", flush=True)
        if out.shape != shape:
            raise AssertionError(f"ulysses output shape {out.shape} != {shape}")
        if len(on) != n:
            raise AssertionError(f"degree {n} output lies on {len(on)} devices")
        if not rel <= ULYSSES_REL_TOL:
            raise AssertionError(f"ulysses degree {n} differs: {rel:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve sd3 on one chip; 4: Ulysses SP check only")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _keep_cpu_backend()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found platform {dev.platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2

    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    print(f"versions: {json.dumps(_versions())}", flush=True)
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}", flush=True)

    t0 = time.perf_counter()
    if args.chips == 4:
        from repro.configs import get
        cfg = get("sd3")
        check_ulysses(devices, cfg.latent_tokens(1024) + COND_LEN,
                      cfg.dit.num_heads, cfg.dit.d_model // cfg.dit.num_heads,
                      (2, 4), args.seed)
    else:
        serve(args.seed)
    print(f"total wall {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
