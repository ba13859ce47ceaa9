"""Offline Profiler (§5.1): latency/memory statistics per stage × degree.

On real hardware this measures; here it *derives* the tables from a
roofline-style analytic model over the actual JAX model configs (param
bytes come from ``jax.eval_shape`` over the real ``init`` functions, so
they are exact) with TPU v5e constants.  The same model backs the
discrete-event simulator, so planner decisions and "measured" outcomes are
consistent — which is precisely the paper's strong-predictability premise
[§5.1: "Leveraging the strong predictability of execution time and memory
footprint in GVT workloads"].

Calibration targets (validated in tests/test_profiler.py):
  * Diffuse scales well with SP at high resolution, poorly at low (Fig. 3);
  * Decode is memory/ICI-bound and scales poorly (Fig. 3);
  * Encode barely benefits from parallelism (§3);
  * Diffuse dominates end-to-end time (> 70%, §2.1/Fig. 8).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.request import Request
from repro.models import diffusion, pipeline as pipe_lib, transformer

# --- TPU v5e hardware constants (per chip) ---------------------------------
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link
DCN_BW = 25e9                # bytes/s per host (inter-pod)
HOST_BW = 10e9               # host<->device staging path
HBM_BYTES = 16 * 2 ** 30     # 16 GiB
MEM_RESERVE = 512 * 2 ** 20  # per-chip runtime reserve (compiler scratch etc.)
MFU = 0.5                    # sustained matmul efficiency (long sequences)
MFU_CONV = 0.12              # conv stacks (<=128ch) utilize the MXU poorly
SEQ_MFU_KNEE = 384           # per-chip tokens below which MFU degrades
DISPATCH_OVERHEAD = 0.004    # s, per-dispatch CPU scheduling cost
COMM_GROUP_INIT = 0.05       # s, lazy (non-hot-set) communicator build


def _seq_mfu(l_per_chip: float) -> float:
    """MFU falls off when the per-chip sequence shard is small — sliced
    matmuls stop saturating the MXU.  This is what makes low-resolution
    requests prefer small SP degrees (Fig. 3's crossing curves)."""
    return MFU * l_per_chip / (l_per_chip + SEQ_MFU_KNEE)

PARALLEL_DEGREES = (1, 2, 4, 8, 16, 32)  # >8 reachable only with cross-node SP
EFFICIENCY_THRESHOLD = 0.8   # paper footnote 4/5


def _count_bytes(shapes) -> int:
    return sum(int(x.size) * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(shapes))


@dataclasses.dataclass(frozen=True)
class StageModelInfo:
    params: int          # parameter count
    bytes: int           # parameter bytes
    num_layers: int
    d_model: int


class Profiler:
    """Cost/memory model for one diffusion pipeline."""

    def __init__(self, cfg: pipe_lib.PipelineConfig,
                 force_k_min: Optional[int] = None,
                 cross_node_sp: bool = False):
        self.cfg = cfg
        self.info = self._stage_infos(cfg)
        # force_k_min=1 models baselines that do not use the App.-E.2 MP fold
        self.k_min = force_k_min if force_k_min else self._compute_k_min()
        # SP instances are intra-node in the paper (§6.2, a PCIe-box
        # constraint); on a TPU pod ICI spans every chip, so cross-node SP
        # is viable (beyond-paper; measured in EXPERIMENTS.md §Perf) —
        # degrees then extend to 32 units, still filtered by efficiency
        self.cross_node_sp = cross_node_sp
        base = max(1, 8 // self.k_min)
        self.max_degree_units = 32 // self.k_min if cross_node_sp else base
        # memo tables keyed by request class — request mixes repeat heavily,
        # exactly the paper's "pre-profiled candidate resolutions" (§5.1)
        self._time_memo: Dict[Tuple, float] = {}
        self._deg_memo: Dict[Tuple, int] = {}
        self._fits_memo: Dict[Tuple, bool] = {}
        self._batch_memo: Dict[Tuple, float] = {}

    @staticmethod
    def _class_key(req: Request) -> Tuple:
        """Workload-class memo key: (pipeline, resolution, seconds) + prompt."""
        return req.key() + (req.cond_len,)

    # -- static model facts --------------------------------------------------

    @staticmethod
    @functools.lru_cache(maxsize=None)
    def _stage_infos_cached(cfg: pipe_lib.PipelineConfig):
        # an abstract key: planning traces shapes only, never touches a device
        key = jax.ShapeDtypeStruct((2,), jnp.uint32)
        enc = jax.eval_shape(lambda k: transformer.init(cfg.encoder, k), key)
        dit = jax.eval_shape(lambda k: diffusion.init(cfg.dit, k), key)
        dec = jax.eval_shape(lambda k: diffusion.init_decoder(cfg.decoder, k), key)
        mk = lambda tree, nl, dm: StageModelInfo(
            params=sum(int(x.size) for x in jax.tree_util.tree_leaves(tree)),
            bytes=_count_bytes(tree), num_layers=nl, d_model=dm)
        return {
            "E": mk(enc, cfg.encoder.num_layers, cfg.encoder.d_model),
            "D": mk(dit, cfg.dit.num_layers, cfg.dit.d_model),
            "C": mk(dec, cfg.decoder.num_upsamples, cfg.decoder.base_channels),
        }

    def _stage_infos(self, cfg):
        return self._stage_infos_cached(cfg)

    def _compute_k_min(self) -> int:
        """Smallest power-of-two chips/unit so the Diffusion model's MP shard
        fits one chip with headroom (App. E.2)."""
        need = self.info["D"].bytes * 1.25
        k = 1
        while need / k > HBM_BYTES * 0.9 and k < 8:
            k *= 2
        return k

    # -- workload geometry ----------------------------------------------------

    def proc_len(self, req: Request, stage: str) -> int:
        return pipe_lib.stage_proc_len(self.cfg, stage, req.resolution,
                                       req.seconds, req.cond_len)

    def latent_tokens(self, req: Request) -> int:
        return self.cfg.latent_tokens(req.resolution, req.seconds)

    # -- FLOPs / bytes per stage ----------------------------------------------

    def stage_flops(self, req: Request, stage: str) -> float:
        """With guidance, E encodes the empty prompt too and D runs each
        step on a batch of two."""
        rows = self.cfg.dit.cond_batch
        if stage == "E":
            i = self.info["E"]
            l = req.cond_len
            return rows * (2.0 * i.params * l + 4.0 * i.num_layers * l * l * i.d_model)
        if stage == "D":
            i = self.info["D"]
            l = self.latent_tokens(req) + req.cond_len
            per_step = 2.0 * i.params * l + 4.0 * i.num_layers * l * l * i.d_model
            return rows * per_step * self.cfg.num_steps
        flops, _, _ = self._decoder_cost(req)
        return flops

    def _decoder_cost(self, req: Request) -> Tuple[float, float, float]:
        """(flops, activation_bytes, hbm_traffic) for the AE decoder.

        Models the *real* AE-KL decoder cost: residual conv blocks per level,
        3D (27-point) kernels + temporal upsampling for video — the JAX
        reference decoder is 2D-per-frame, but the serving planner must see
        the production decoder's cost profile (DESIGN.md §assumptions).
        """
        dec = self.cfg.decoder
        f_lat, h, w = self.cfg.latent_grid(req.resolution, req.seconds)
        kernel = 18 if self.cfg.is_video else 9  # video AEs use factorized 2+1D convs
        convs = 1 + 2 * dec.res_blocks     # per level (res blocks = 2 convs)
        flops = act = 0.0
        for lvl in range(dec.num_upsamples + 1):
            spatial = 4 * h * w * 4 ** lvl    # after un-patchify: 2h x 2w
            frames = (f_lat * (2 ** min(lvl, 2))) if self.cfg.is_video else 1
            cc = max(dec.base_channels // (2 ** lvl), 128)
            flops += spatial * frames * cc * cc * kernel * 2 * convs
            act += spatial * frames * cc * 2 * convs
        return flops, act, self.info["C"].bytes + act * 2

    def stage_act_bytes(self, req: Request, stage: str) -> float:
        """Peak activation bytes at degree 1 (shards ~1/k with SP); the
        guided batch holds two rows."""
        rows = self.cfg.dit.cond_batch
        if stage == "E":
            return rows * req.cond_len * self.info["E"].d_model * 2 * 12
        if stage == "D":
            l = self.latent_tokens(req) + req.cond_len
            return rows * l * self.info["D"].d_model * 2 * 24
        _, act, _ = self._decoder_cost(req)
        return act

    def d_live_bytes(self, req: Request) -> float:
        """Bytes D holds live at its peak: one layer's forward pass keeps
        the stream, its normed copy, q, k, v, the attention's output and
        the MLP's hidden of 4d, ten (l, d) bf16 tensors per row (the 480-px
        cogvideox D program compiled for a v5e holds 2.06 GiB, 9.9 per row
        of its guided batch)."""
        l = self.latent_tokens(req) + req.cond_len
        return self.cfg.dit.cond_batch * l * self.info["D"].d_model * 2 * 10

    def stage_hbm_bytes(self, req: Request, stage: str) -> float:
        """Total HBM traffic (params re-read per step + activations)."""
        if stage == "E":
            return self.info["E"].bytes + self.stage_act_bytes(req, "E") * 2
        if stage == "D":
            return (self.info["D"].bytes + self.stage_act_bytes(req, "D") * 4
                    ) * self.cfg.num_steps
        _, _, hbm = self._decoder_cost(req)
        return hbm

    # -- latency model ---------------------------------------------------------

    def stage_time(self, req: Request, stage: str, k_chips: int) -> float:
        """Wall-clock estimate of stage ``stage`` at SP degree ``k_chips``."""
        key = (req.pipeline, req.resolution, req.seconds, req.cond_len,
               stage, k_chips)
        hit = self._time_memo.get(key)
        if hit is not None:
            return hit
        t = self._stage_time_impl(req, stage, k_chips)
        self._time_memo[key] = t
        return t

    def _stage_time_impl(self, req: Request, stage: str, k_chips: int) -> float:
        flops = self.stage_flops(req, stage)
        hbm = self.stage_hbm_bytes(req, stage)
        if stage == "E":
            # batching-friendly, parallelism-averse: capped speedup
            speed = min(k_chips, 1.3)
            return (max(flops / (PEAK_FLOPS * MFU), hbm / HBM_BW) / speed
                    + (k_chips - 1) * 2e-3 + DISPATCH_OVERHEAD)
        if stage == "D":
            i = self.info["D"]
            l = self.latent_tokens(req) + req.cond_len
            compute = flops / (k_chips * PEAK_FLOPS * _seq_mfu(l / k_chips))
            mem = hbm / (k_chips * HBM_BW)
            # Ulysses: 2 all-to-alls per layer per step; (k-1)/k^2 wire factor
            a2a = l * i.d_model * 2
            comm = (self.cfg.num_steps * i.num_layers * 2 * a2a
                    * (k_chips - 1) / (k_chips ** 2) / ICI_BW) if k_chips > 1 else 0.0
            return max(compute, mem) + comm + DISPATCH_OVERHEAD
        # Decode: conv pyramid; halo exchange + per-chip launch overhead make
        # spatial sharding scale poorly (paper Fig. 3 right)
        mem = hbm / (k_chips * HBM_BW)
        compute = flops / (k_chips * PEAK_FLOPS * MFU_CONV)
        comm = ((self.stage_act_bytes(req, "C") * 0.3 * (k_chips - 1)
                 / k_chips / ICI_BW) + (k_chips - 1) * 2e-3) if k_chips > 1 else 0.0
        return max(mem, compute) + comm + DISPATCH_OVERHEAD

    def batched_stage_time(self, req: Request, stage: str, k_chips: int,
                           batch: int) -> float:
        """Latency of serving ``batch`` identical requests in one run
        (App. E.1): compute-bound work amortizes per-item; activation
        traffic scales linearly."""
        if batch <= 1:
            return self.stage_time(req, stage, k_chips)
        key = (req.pipeline, req.resolution, req.seconds, req.cond_len,
               stage, k_chips, batch)
        hit = self._batch_memo.get(key)
        if hit is not None:
            return hit
        flops = self.stage_flops(req, stage) * batch
        hbm = (self.stage_hbm_bytes(req, stage)
               + (batch - 1) * self.stage_act_bytes(req, stage) * 3)
        base = self.stage_time(req, stage, k_chips)
        mfu = MFU_CONV if stage == "C" else MFU
        t = max(flops / (k_chips * PEAK_FLOPS * mfu),
                hbm / (k_chips * HBM_BW)) + DISPATCH_OVERHEAD
        t = max(base, t)
        self._batch_memo[key] = t
        return t

    def optimal_batch(self, req: Request, stage: str, k_chips: int,
                      cap: int = 8) -> int:
        """Largest batch whose latency stays within 1.2x single (E.1)."""
        key = (req.pipeline, req.resolution, req.seconds, req.cond_len,
               stage, k_chips, "bs")
        hit = self._deg_memo.get(key)
        if hit is not None:
            return hit
        t1 = self.stage_time(req, stage, k_chips)
        best = 1
        bs = 2
        while bs <= cap:
            if self.batched_stage_time(req, stage, k_chips, bs) <= 1.2 * t1:
                best = bs
            bs *= 2
        self._deg_memo[key] = best
        return best

    def speedup(self, req: Request, stage: str, k_chips: int) -> float:
        return self.stage_time(req, stage, 1) / self.stage_time(req, stage, k_chips)

    def efficiency(self, req: Request, stage: str, k_chips: int) -> float:
        return self.speedup(req, stage, k_chips) / k_chips

    def optimal_degree(self, req: Request, stage: str) -> int:
        """Paper's *optimal parallelism strategy*: highest degree with
        efficiency > 0.8 (footnote 4). In scheduling *units*."""
        key = (req.pipeline, req.resolution, req.seconds, req.cond_len,
               stage)
        hit = self._deg_memo.get(key)
        if hit is not None:
            return hit
        best = 1
        for k in PARALLEL_DEGREES:
            if k > self.max_degree_units:
                break
            if self.efficiency(req, stage, k * self.k_min) > EFFICIENCY_THRESHOLD:
                best = k
        self._deg_memo[key] = best
        return best

    def pipeline_time(self, req: Request, k_chips: Optional[int] = None) -> float:
        """End-to-end time at per-stage optimal (used for SLO = 2.5x this)."""
        total = 0.0
        for s in ("E", "D", "C"):
            k = k_chips or self.optimal_degree(req, s) * self.k_min
            total += self.stage_time(req, s, k)
        return total

    # -- memory feasibility ------------------------------------------------------

    def unit_param_bytes(self, ptype: str) -> float:
        """Per-chip parameter bytes for a placement type (MP folds /k_min)."""
        return sum(self.info[s].bytes for s in ptype) / self.k_min

    def peak_mem(self, req: Request, ptype: str, k_units: int) -> float:
        """Per-chip peak bytes running the heaviest stage of ``ptype`` for
        ``req`` at degree ``k_units`` (SP shards activations, not params).

        Decode activations are capped at the tiled-decode working set (VAE
        tiling is standard practice; the *time* model still pays the full
        HBM traffic)."""
        k_chips = k_units * self.k_min

        def act(s):
            if s == "D":
                return self.d_live_bytes(req) / k_chips
            a = self.stage_act_bytes(req, s) / k_chips
            return min(a, 4 * 2 ** 30) if s == "C" else a

        peak = max(act(s) for s in ptype)
        return self.unit_param_bytes(ptype) + peak + MEM_RESERVE

    def fits(self, req: Request, ptype: str, k_units: int) -> bool:
        """Memory-feasibility filter F_{r,i,k} — memoized: it sits on the
        dispatch hot path (called per pending request x VR type x degree,
        every scheduler wake-up)."""
        key = (req.pipeline, req.resolution, req.seconds, req.cond_len,
               ptype, k_units)
        hit = self._fits_memo.get(key)
        if hit is None:
            hit = self.peak_mem(req, ptype, k_units) <= HBM_BYTES
            self._fits_memo[key] = hit
        return hit

    # -- inter-stage communication -------------------------------------------------

    def comm_bytes(self, req: Request, edge: str) -> float:
        """Q_ED / Q_DC tensor volumes (bf16)."""
        if edge == "ED":
            return self.cfg.dit.cond_batch * req.cond_len * self.info["E"].d_model * 2.0
        if edge == "DC":
            return self.latent_tokens(req) * self.cfg.dit.latent_dim * 2.0
        raise KeyError(edge)

    def transfer_time(self, nbytes: float, intra_node: bool) -> float:
        return nbytes / (ICI_BW if intra_node else DCN_BW) + 2e-4

    def stage_load_time(self, stage: str, via_host: bool) -> float:
        """Adjust-on-Dispatch replica load (P2P peer vs pinned-host path)."""
        per_chip = self.info[stage].bytes / self.k_min
        return per_chip / (HOST_BW if via_host else ICI_BW) + 1e-3
