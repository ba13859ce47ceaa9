"""Quickstart: generate an image with a tiny diffusion pipeline, then serve
three requests stage-by-stage with the real TridentServe planners.

  PYTHONPATH=src python examples/quickstart.py
"""
import jax
import numpy as np

import repro.configs as C
from repro.core.dispatcher import Dispatcher
from repro.core.orchestrator import Orchestrator
from repro.core.profiler import Profiler
from repro.core.request import Request
from repro.launch.compile_cache import enable_compile_cache
from repro.models import pipeline as pl


def main():
    enable_compile_cache()
    # --- 1. a runnable (reduced) Stable-Diffusion-3-style pipeline ---------
    cfg = C.get_smoke("sd3")
    params = pl.init(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0,
                                cfg.encoder.vocab_size)
    image = pl.generate(cfg, params, prompt, resolution=64, seconds=0.0,
                        key=jax.random.PRNGKey(2))
    print(f"generated image: shape={image.shape} "
          f"range=[{float(image.min()):.2f}, {float(image.max()):.2f}]")

    # --- 2. plan placement + dispatch with the paper's algorithms ----------
    prof = Profiler(C.get("sd3"))        # full-size cost model drives plans
    orch = Orchestrator(prof, num_chips=32)
    reqs = []
    for res in (512, 1024, 1536):
        r = Request("sd3", res)
        r.deadline = 2.5 * prof.pipeline_time(r)
        reqs.append(r)
    plan = orch.generate(reqs)
    print(f"placement plan (32 chips): {plan.type_histogram()}")
    disp = Dispatcher(prof)
    idle = set(range(plan.num_units))
    decisions = disp.dispatch(reqs, plan, idle, {g: 0.0 for g in idle}, 0.0)
    for d in decisions:
        print(f"  req res={d.request.resolution}: VR type V{d.vr_type}, "
              f"Diffuse on units {d.d_units} (degree {d.degree}), "
              f"E on {d.e_units}, C on {d.c_units}")

    # --- 3. execute one dispatched request end-to-end ----------------------
    d = decisions[0]
    cond = pl.encode(cfg, params, prompt)                     # Γ^E
    lat = pl.diffuse(cfg, params, cond,
                     (1, cfg.latent_tokens(64, 0.0), cfg.dit.latent_dim),
                     jax.random.PRNGKey(3))                   # Γ^D
    out = pl.decode(cfg, params, lat, cfg.latent_grid(64, 0.0))  # Γ^C
    assert np.isfinite(np.asarray(out)).all()
    print(f"stage-level execution OK: output {out.shape}")


if __name__ == "__main__":
    main()
