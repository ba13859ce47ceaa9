"""Distributed training entry point.

Builds a mesh over the available devices, shards the TrainState with the
partition rules (+ optional ZeRO/FSDP/seq-shard switches from §Perf), and
runs the training loop on sharded synthetic batches.

  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --steps 20 --smoke
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.train --arch deepseek-moe-16b \
    --smoke --steps 10 --mesh 4x2 --opt zero
"""
import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--mesh", default=None,
                    help="DxM data x model mesh (default: all devices x 1)")
    ap.add_argument("--opt", default="",
                    help="comma list: zero,fsdp,seqshard (§Perf switches)")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()
    opts = frozenset(o for o in args.opt.split(",") if o)

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import repro.configs as C
    from repro.data import pipeline as dp
    from repro.launch.mesh import make_mesh
    from repro.models import transformer
    from repro.sharding import partition
    from repro.training import loop

    cfg = C.get_smoke(args.arch) if args.smoke else C.get(args.arch)
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
    else:
        d, m = len(jax.devices()), 1
    mesh = make_mesh((d, m), ("data", "model"))
    print(f"arch={cfg.name} mesh={d}x{m} devices={len(jax.devices())} "
          f"opts={sorted(opts)}")

    state = loop.init_state(cfg, jax.random.PRNGKey(0))
    state_shape = jax.eval_shape(lambda: state)
    sspec = partition.state_specs(
        cfg, state_shape,
        zero_mesh=mesh if ("zero" in opts or "fsdp" in opts) else None,
        fsdp="fsdp" in opts)
    sspec = partition.validate_divisibility(sspec, state_shape, mesh)
    shard = partition.named(sspec, mesh)
    state = jax.device_put(state, shard)
    if "seqshard" in opts:
        transformer.set_activation_sharding(
            NamedSharding(mesh, P("data", "model", None)))

    dcfg = dp.DataConfig(batch=args.batch, seq_len=args.seq)
    step_fn = jax.jit(loop.make_train_step(cfg), in_shardings=(shard, None),
                      donate_argnums=(0,))
    t0 = time.perf_counter()
    with mesh:
        for i in range(args.steps):
            batch = {k: jax.device_put(jnp.asarray(v), NamedSharding(
                         mesh, P("data", *([None] * (v.ndim - 1)))))
                     for k, v in dp.synthetic_batch(cfg, dcfg, i).items()}
            state, metrics = step_fn(state, batch)
            if i % args.log_every == 0 or i == args.steps - 1:
                print(f"step {i:4d} loss {float(metrics['loss']):.4f} "
                      f"gnorm {float(metrics['grad_norm']):.2f} "
                      f"wall {time.perf_counter() - t0:.1f}s", flush=True)
    transformer.set_activation_sharding(None)


if __name__ == "__main__":
    main()
