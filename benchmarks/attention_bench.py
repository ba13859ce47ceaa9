"""D's attention core on one TPU chip: XLA's materialised float32 scores
(``repro.models.common.attention``, what the DiT runs below the kernel's
threshold) against the Pallas flash kernel (``repro.kernels.flash_attention``)
and jax's splash attention as a yardstick, at the DiT's joint lengths.

    python benchmarks/attention_bench.py [--sweep] [--out PATH]

One process, on a TPU (exits 2 without one).  Each implementation runs
``--reps`` times inside one jitted loop that feeds its output back as the
next query, so the host's launch cost is spread over the loop; the time per
call is the best of three such loops.  TFLOP/s counts ``4 l^2 d`` per head
(the two products; the softmax left out).  Splash runs on the length padded
to 128 with a full mask (no masking of the padded keys), so it does a little
less than the kernel.  ``--sweep`` also times the kernel's block sizes.
Prints one JSON line per measurement and writes them all to ``--out``.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa
from repro.models import common

# (joint length, heads, head dim): sd3 at 128 / 256 / 512 px, flux at 1024 px
SHAPES = [(141, 24, 64), (333, 24, 64), (1101, 24, 64), (4173, 24, 128)]
LONG = [(16461, 24, 128)]          # flux at 2048 px: the scores would not fit
SWEEP = [(256, 512), (512, 256), (512, 1024), (1024, 512), (1024, 1024)]


def _loop(f, reps):
    def run(q, k, v):
        return jax.lax.fori_loop(0, reps, lambda i, x: f(x, k, v).astype(x.dtype), q)
    return jax.jit(run)


def _time(f, args, reps) -> float:
    g = _loop(f, reps)
    g(*args).block_until_ready()
    best = math.inf
    for _ in range(3):
        t = time.perf_counter()
        g(*args).block_until_ready()
        best = min(best, time.perf_counter() - t)
    return best / reps


def _splash(l, h, d):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    lp = -(-l // 128) * 128
    blk = next(b for b in (512, 384, 256, 128) if lp % b == 0)
    mask = sm.MultiHeadMask([sm.FullMask((lp, lp))] * h)
    kern = sk.make_splash_mha_single_device(
        mask, block_sizes=sk.BlockSizes(block_q=blk, block_kv=blk, block_kv_compute=blk))
    scale = 1.0 / math.sqrt(d)

    def f(q, k, v):                  # (1, l, h, d), padded to lp inside
        pad = lambda x: jnp.pad(jnp.moveaxis(x[0], 1, 0), ((0, 0), (0, lp - l), (0, 0)))
        o = kern(pad(q * scale), pad(k), pad(v))
        return jnp.moveaxis(o[:, :l], 0, 1)[None]
    return f, blk


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--out", default="chiprun_out/attention_bench.json")
    a = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform}", file=sys.stderr)
        return 2
    rows = []

    def emit(**r):
        r.update(device=dev.device_kind)
        rows.append(r)
        print(json.dumps(r), flush=True)

    xla = lambda q, k, v: common.attention(q, k, v, None)
    for l, h, d in SHAPES + LONG:
        ks = jax.random.split(jax.random.PRNGKey(l), 3)
        q, k, v = (jax.random.normal(kk, (1, l, h, d), jnp.bfloat16) for kk in ks)
        flops = 4.0 * l * l * d * h
        impls = [("kernel", functools.partial(fa.flash_attention, causal=False), {})]
        if (l, h, d) in SHAPES:
            impls.insert(0, ("xla", xla, {}))
            if a.sweep:
                impls += [("kernel", functools.partial(fa.flash_attention, causal=False,
                                                       block_q=bq, block_k=bk),
                           {"block_q": bq, "block_k": bk}) for bq, bk in SWEEP]
        try:
            f, blk = _splash(l, h, d)
            impls.append(("splash", f, {"block": blk}))
        except Exception as e:  # noqa: BLE001 — the yardstick may refuse a shape
            emit(l=l, heads=h, head_dim=d, impl="splash", error=repr(e)[:200])
        want = jax.jit(xla)(q, k, v).astype(jnp.float32) if (l, h, d) in SHAPES else None
        for name, f, extra in impls:
            try:
                t = _time(f, (q, k, v), a.reps)
            except Exception as e:  # noqa: BLE001 — record a refused compile and go on
                emit(l=l, heads=h, head_dim=d, impl=name, error=repr(e)[:200], **extra)
                continue
            r = dict(l=l, heads=h, head_dim=d, impl=name, ms=t * 1e3,
                     tflops=flops / t / 1e12, **extra)
            if want is not None:
                got = jax.jit(f)(q, k, v).astype(jnp.float32)
                r["rel_err_vs_xla"] = float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))
            emit(**r)
    os.makedirs(os.path.dirname(a.out) or ".", exist_ok=True)
    with open(a.out, "w") as fh:
        json.dump(rows, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
