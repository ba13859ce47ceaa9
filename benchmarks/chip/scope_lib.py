"""Device time by named scope, and the work of a scope, for the metric
readers in ``metrics/``.

The stage programs run their blocks under ``jax.named_scope`` (names in
``repro.models.scopes``: ``dit/attention``, ``dit/mlp``, ``decoder/up0``,
...).  A TPU trace carries no scope: its ``XLA Ops`` events hold only the
HLO instruction (``trace.Summary.ops`` keeps its program, name and result
type).  So the map from instruction to scope comes from the compiled text:
each stage program in the trace is lowered again from the family's stage
function, with the shapes and placement the harness gave it, which gives
the same module, and so the same executable (a hit in the compile cache),
as the one that ran; ``repro.roofline.hlo.op_scopes`` reads its scopes.

``scope_seconds`` puts each operation's device seconds under its scope.
A fusion that computes the matrix products of several scopes, as the
compiler may make across a scope boundary, goes under their names joined
by ``+`` (``dit/attention+dit/qkv``): the trace cannot split its time.
What no scope claims goes to ``unscoped``, and so does an operation whose
name and result type the rebuilt text does not hold.  Per program, the
scopes' seconds and ``unscoped`` add up to the seconds of its operations
in ``Summary.ops``.  A program built without named scopes has no
``repro.models.scopes``: then nothing is read.
"""
from __future__ import annotations

from benchmarks.chip import trace
from benchmarks.chip import traffic as tr
from benchmarks.chip.metric_lib import programs

try:
    from repro.models import scopes as program_scopes
    from repro.roofline.hlo import fused_work_scopes, op_scopes
except ImportError:                    # a program without named scopes
    program_scopes = None

UNSCOPED = "unscoped"


def attention_flops(fam, res: int) -> float:
    """Operations of D's attention core for one request: ``4 l^2 d`` per
    layer and step (the scores and the weighted sum, two per multiply-add;
    the softmax left out, so a floor), ``l`` the joint length."""
    c = fam.cfg
    l = fam.latent_tokens(res) + fam.cond_len
    return 4.0 * l * l * c["dit_d_model"] * c["dit_layers"] * fam.steps


def stage_texts(run) -> dict:
    """{program name: compiled HLO text} of each stage program in the
    trace, rebuilt as the harness built it."""
    if program_scopes is None or run.trace is None or not run.trace.programs:
        return {}
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    fam = run.fam
    pcfg = fam.program_config()
    shapes = run.param_shapes
    # the request's inputs sit on the chip (committed); the weights do not
    on = SingleDeviceSharding(jax.devices()[0])
    placed = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=on)
    out = {}
    for res in tr.resolutions(run.cell.traffic):
        fns = fam.stage_fns(pcfg, res)
        for name, fn in fns.values():
            fn.__name__ = fn.__qualname__ = name
        tokens = placed(jax.ShapeDtypeStruct((1, fam.cond_len), jnp.int32))
        cond = placed(jax.eval_shape(fns["E"][1], shapes["encode"], tokens))
        key = placed(jax.ShapeDtypeStruct((2,), jnp.uint32))
        latents = placed(jax.eval_shape(fns["D"][1], shapes["diffuse"], cond, key))
        args = {"E": (shapes["encode"], tokens), "D": (shapes["diffuse"], cond, key),
                "C": (shapes["decode"], latents)}
        for stage, (name, fn) in fns.items():
            if name in run.trace.programs and name not in out:
                out[name] = jax.jit(fn).lower(*args[stage]).compile().as_text()
    return out


def scope_seconds(run, texts=None) -> dict:
    """{program: {scope: device seconds}} over the traced window, with an
    ``unscoped`` entry in every program."""
    texts = stage_texts(run) if texts is None else texts
    if not texts:
        return {}
    names = program_scopes.names(run.cell.config["decoder_upsamples"])
    known, where = {}, {}
    for prog, text in texts.items():
        known[prog] = {trace._op_label(line.strip().removeprefix("ROOT "))
                       for line in text.splitlines() if " = " in line}
        where[prog] = op_scopes(text, names)
        for op, work in fused_work_scopes(text, names).items():
            own = {where[prog][op]} if op in where[prog] else set()
            where[prog][op] = "+".join(sorted(work | own))
    out = {prog: {UNSCOPED: 0.0} for prog in texts}
    for label, secs in run.trace.ops.items():
        prog, _, op = label.partition(" ")
        if prog not in texts:
            continue
        name = op.split(" ")[0]
        scope = where[prog].get(name, UNSCOPED) if op in known[prog] else UNSCOPED
        out[prog][scope] = out[prog].get(scope, 0.0) + secs
    return out


def attention_mfu(run, by_prog: dict):
    """D's attention core's share of the chip's peak, %: the core's
    operations in the D runs of the trace over the device seconds of
    ``dit/attention`` in D's programs, with the fusions it shares with
    another scope, times peak; None where that scope has no time."""
    if not by_prog:
        return None
    secs = sum(sorted(t for prog, v in by_prog.items() if prog.startswith("stage_D_")
                      for k, t in v.items()
                      if program_scopes.DIT_ATTENTION in k.split("+")))
    if secs <= 0:
        return None
    work = sum(attention_flops(run.fam, res) * n
               for st, res, _, n in programs(run) if st == "D")
    return 100.0 * work / (secs * run.peaks["flops_bf16"])


def report(by_prog: dict) -> list:
    """One line per program: each scope's share of its operations' device
    time, the largest first, and the unscoped share."""
    out = []
    for prog, v in sorted(by_prog.items()):
        total = sum(sorted(v.values()))
        if total <= 0:
            continue
        shares = sorted(((s, k) for k, s in v.items() if k != UNSCOPED), reverse=True)
        out.append(f"scopes: {prog} {total:.4f} s: " + ", ".join(
            f"{k} {100 * s / total:.1f}%" for s, k in shares)
            + f"; {UNSCOPED} {100 * v[UNSCOPED] / total:.2f}%")
    return out
