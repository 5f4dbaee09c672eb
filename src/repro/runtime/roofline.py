"""Roofline model — per-device peaks + the three terms.

    compute    = HLO_FLOPs_per_device / peak_FLOP/s
    memory     = HLO_bytes_per_device / HBM_bw
    collective = per-device link bytes / link_bw

``compiled.cost_analysis()`` on a partitioned executable reports *per-device*
program costs (the analyzed module is the per-device HLO), so terms divide by
per-chip rates directly; the brief's "/(chips × rate)" form is equivalent.

MODEL_FLOPS uses 6·N·D (dense train), 6·N_active·D (MoE), and matching
analytic forms for prefill/decode (incl. attention and KV-read bytes).

The measured-machine section at the bottom (:func:`simulate_stream`,
:func:`stream_lane_seconds`) replaces these *datasheet* constants with
*calibrated* per-stage cost functions from ``runtime/calibrate.py``: it
replays the lane-overlapped ``ChunkedPipeline`` schedule (main-thread H2D
staging, compute lane, io lane, in-flight ``window`` anti-dependency)
through the event-driven ``TimelineSimulator`` to predict a stream's
makespan for a candidate (chunk size, window) — the solver substrate of
``core/tuner.py``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np

from ..configs.base import ModelConfig, ShapeConfig



@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one device kind."""

    bf16_flops: float   # FLOP/s
    hbm_bw: float       # B/s
    hbm_bytes: float    # B
    ici_bw: float       # B/s per link


# Keyed by ``jax.Device.device_kind``.  TPU v5e: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
_V5E = DevicePeaks(bf16_flops=197e12, hbm_bw=819e9, hbm_bytes=16e9, ici_bw=50e9)
PEAKS: dict[str, DevicePeaks] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}
V5E_KIND = "TPU v5 lite"


def peaks(device_kind: str | None = None) -> DevicePeaks:
    """Peaks of ``device_kind`` (default: the first local device's kind).

    A kind the table does not know raises: a roofline against another
    chip's peaks would be a number under the wrong device's name.
    """
    kind = jax.devices()[0].device_kind if device_kind is None else device_kind
    try:
        return PEAKS[kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {kind!r}; known: {sorted(PEAKS)}"
        ) from None


@dataclass
class RooflineTerms:
    t_compute: float
    t_memory: float
    t_collective: float
    flops: float
    bytes_accessed: float
    link_bytes: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    def to_dict(self) -> dict:
        return {
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "flops_per_device": self.flops,
            "bytes_per_device": self.bytes_accessed,
            "link_bytes_per_device": self.link_bytes,
        }


def terms_from_analysis(
    cost: dict | None, link_bytes: float, flops_override: float | None = None,
    *, device_kind: str | None = None,
) -> RooflineTerms:
    pk = peaks(device_kind)
    flops = float(flops_override if flops_override is not None else (cost or {}).get("flops", 0.0))
    nbytes = float((cost or {}).get("bytes accessed", 0.0))
    return RooflineTerms(
        t_compute=flops / pk.bf16_flops,
        t_memory=nbytes / pk.hbm_bw,
        t_collective=link_bytes / pk.ici_bw,
        flops=flops,
        bytes_accessed=nbytes,
        link_bytes=link_bytes,
    )


# ---------------------------------------------------------------------------
# analytic MODEL_FLOPS
# ---------------------------------------------------------------------------


def count_params(params_shape) -> dict:
    """Split param counts: embedding / expert / other (from an eval_shape tree)."""
    import jax.tree_util as jtu

    counts = {"embed": 0, "expert": 0, "other": 0}
    for path, leaf in jtu.tree_flatten_with_path(params_shape)[0]:
        names = [str(getattr(e, "key", getattr(e, "idx", ""))) for e in path]
        n = int(np.prod(leaf.shape))
        if "table" in names or ("head" in names):
            counts["embed"] += n
        elif "moe" in names and names[-1] in {"wg", "wu", "wd"}:
            counts["expert"] += n
        else:
            counts["other"] += n
    return counts


def active_params(cfg: ModelConfig, counts: dict) -> float:
    """N_active: experts scaled by (top_k + shared-equivalent)/n_experts."""
    n = counts["other"]
    if cfg.moe is not None and counts["expert"]:
        frac = cfg.moe.top_k / max(cfg.moe.n_experts, 1)
        n += counts["expert"] * frac
        # shared experts are inside "other" via the shared swiglu params
    return float(n)


def model_flops(cfg: ModelConfig, shape: ShapeConfig, counts: dict) -> dict:
    """Analytic FLOPs for the whole (global) step + useful-compute ratio base."""
    hd = cfg.resolved_head_dim
    n_act = active_params(cfg, counts)
    n_total = float(counts["other"] + counts["expert"])
    b, s = shape.global_batch, shape.seq_len
    tokens = b * s
    attn_layers = cfg.n_layers
    if cfg.family == "ssm":
        attn_layers = 0
    if cfg.family == "hybrid":
        attn_layers = cfg.n_layers // 3  # 1-in-3 local attention
        s_eff = min(s, cfg.hybrid.window)
    else:
        s_eff = s

    if shape.kind == "train":
        mm = 6.0 * n_act * tokens
        attn = 3.0 * attn_layers * 2.0 * b * s * s_eff * cfg.n_heads * hd  # fwd≈2·B·S·S_eff·H·hd (causal ≈ /2 folded in)
        return {"model_flops": mm + attn, "matmul_flops": mm, "attn_flops": attn}
    if shape.kind == "prefill":
        mm = 2.0 * n_act * tokens
        attn = attn_layers * 2.0 * b * s * s_eff * cfg.n_heads * hd
        return {"model_flops": mm + attn, "matmul_flops": mm, "attn_flops": attn}
    # decode: one token per sequence; S is the cache length
    mm = 2.0 * n_act * b
    attn = attn_layers * 4.0 * b * min(s, s_eff if cfg.family == "hybrid" else s) * cfg.n_heads * hd
    kv_bytes = _decode_state_bytes(cfg, b, s)
    return {
        "model_flops": mm + attn, "matmul_flops": mm, "attn_flops": attn,
        "state_read_bytes": kv_bytes,
    }


def analytic_memory_bytes(
    cfg: ModelConfig, shape: ShapeConfig, counts: dict,
    bytes_per_device: int, chips: int,
) -> float:
    """Per-device HBM traffic estimate (HLO 'bytes accessed' undercounts
    while-loop bodies, so the memory term uses max(reported, analytic)).

    train:   params f32 read(fwd)+read(bwd)+write + m/v read+write (f32)
             + layer-carry activations write+read (bf16) + logits traffic
    prefill: params read + activations write
    decode:  active params read + state read/write
    """
    p_local = float(bytes_per_device)  # param bytes per device (param_dtype)
    b, s = shape.global_batch, shape.seq_len
    tokens_local = b * (s if shape.kind != "decode" else 1) / chips
    d = cfg.d_model
    act_carry = tokens_local * d * 2.0 * 2.0 * cfg.n_layers  # bf16 write+read
    vocab_local = cfg.vocab / chips
    if shape.kind == "train":
        logits = tokens_local * vocab_local * 4.0 * 3.0 * chips / max(chips, 1)
        return 8.0 * p_local + act_carry + logits
    if shape.kind == "prefill":
        return p_local + act_carry
    # decode
    n_total = max(counts["other"] + counts["expert"], 1)
    active_frac = active_params(cfg, counts) / n_total
    state = _decode_state_bytes(cfg, b, s) / chips
    return p_local * active_frac + 2.0 * state


# ---------------------------------------------------------------------------
# measured-machine stream model (HPDR §V-C auto-tuner substrate)
# ---------------------------------------------------------------------------


def simulate_stream(
    chunk_sizes,
    h2d_time,
    compute_time,
    serialize_time,
    window: int,
    window_overhead_s: float = 0.0,
):
    """Predict the lane-overlapped ``ChunkedPipeline`` makespan.

    Mirrors the *real* scheduler exactly (three lanes, not the Fig. 9
    four-task form): chunk *i* is ``I_i`` (main-thread slice +
    ``device_put``) → ``R_i`` (compute lane) → ``S_i`` (io lane: D2H fetch
    + container serialization), with the bounded-window anti-dependency
    ``I_i ← S_{i-window}``.  ``window=1`` therefore reproduces the fully
    serial schedule.  ``window_overhead_s`` is the calibrated per-chunk
    staging/scheduling cost the pipelined schedule pays over serial
    (thread handoff, future chaining); it is charged on the staging task
    only when ``window > 1``.

    ``h2d_time``/``compute_time``/``serialize_time`` map chunk bytes →
    seconds (e.g. ``AffineCost.time_for`` / ``PhiModel.time_for``).
    Returns ``(makespan_seconds, schedule_dict)``.
    """
    from ..core import pipeline as pl  # lazy: keep layering acyclic

    window = max(1, int(window))
    ov = float(window_overhead_s) if window > 1 else 0.0
    tasks = []
    for i, c in enumerate(chunk_sizes):
        deps = (f"S{i - window}",) if i >= window else ()
        tasks.append(pl.Task(f"I{i}", pl.H2D, h2d_time(c) + ov, deps))
        tasks.append(pl.Task(f"R{i}", pl.COMPUTE, compute_time(c), (f"I{i}",)))
        tasks.append(pl.Task(f"S{i}", pl.D2H, serialize_time(c), (f"R{i}",)))
    sched = pl.TimelineSimulator().run(tasks)
    return pl.TimelineSimulator.makespan(sched), sched


def stream_lane_seconds(
    chunk_sizes, h2d_time, compute_time, serialize_time
) -> dict:
    """Per-lane serial-sum seconds for a chunk schedule (the no-overlap
    bound the measured ``ChunkedResult.lane_seconds()`` is compared to)."""
    return {
        "h2d": sum(h2d_time(c) for c in chunk_sizes),
        "compute": sum(compute_time(c) for c in chunk_sizes),
        "serialize": sum(serialize_time(c) for c in chunk_sizes),
    }


def _decode_state_bytes(cfg: ModelConfig, batch: int, s: int) -> float:
    hd = cfg.resolved_head_dim
    if cfg.family == "ssm":
        ssm = cfg.ssm
        d_inner = ssm.expand * cfg.d_model
        h = d_inner // ssm.head_dim
        return cfg.n_layers * batch * h * ssm.head_dim * ssm.d_state * 4.0
    if cfg.family == "hybrid":
        nsuper = cfg.n_layers // 3
        w = cfg.hybrid.lru_width or cfg.d_model
        rec = 2 * nsuper * batch * w * 4.0
        attn_cache = nsuper * batch * min(s, cfg.hybrid.window) * cfg.n_kv_heads * hd * 2 * 2.0
        return rec + attn_cache
    if cfg.attn_type == "mla":
        m = cfg.mla
        return cfg.n_layers * batch * s * (m.kv_lora_rank + m.qk_rope_head_dim) * 2.0
    return cfg.n_layers * batch * s * cfg.n_kv_heads * hd * 2 * 2.0
