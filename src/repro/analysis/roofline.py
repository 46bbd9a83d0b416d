"""Three-term roofline from the dry-run records + analytic MODEL_FLOPS.

  compute    = flops_per_device / peak FLOP/s
  memory     = bytes_per_device / peak HBM bytes/s
  collective = wire_bytes_per_device / peak bytes/s of one ICI link

The peaks come from :data:`PEAKS`, keyed by ``jax.Device.device_kind``;
every caller names the kind it prices, and a kind not in the table is
an error, not a default.

flops/bytes come from the HLO-text cost model (analysis/hlo.py — XLA's
cost_analysis ignores scan trip counts, see that module).  MODEL_FLOPS is
the analytic useful-work yardstick: 6*N*D for training (N = active
non-embedding params, D = tokens) plus exact attention-window terms;
2*N*D for inference forward passes.  The ratio MODEL_FLOPS / HLO_FLOPs
exposes remat/redundancy waste per cell.
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import jax

from repro.config import ModelConfig, SHAPES, ShapeConfig, layer_kinds


@dataclass(frozen=True)
class DevicePeaks:
    """Published peak rates of one chip."""
    flops: float        # bf16 FLOP/s
    hbm_bw: float       # HBM bytes/s
    link_bw: float      # bytes/s of one chip-to-chip (ICI) link


#: Keyed by ``jax.Device.device_kind``.  Source: Google Cloud
#: documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM, and
#: 1,600 Gbit/s of interchip interconnect per chip over four links.
PEAKS = {
    "TPU v5 lite": DevicePeaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks(device_kind: str) -> DevicePeaks:
    """The published peaks of ``device_kind``; raises for a kind with
    none on record."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peak rates for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


RESULTS = Path(__file__).resolve().parents[3] / "results" / "dryrun"
#: The dry-run (``launch/dryrun.py``) lowers pod meshes of this chip; its
#: records under :data:`RESULTS` are priced as such.
DRYRUN_DEVICE_KIND = "TPU v5 lite"


# ---------------------------------------------------------------------------
# Analytic parameter / FLOP counting
# ---------------------------------------------------------------------------

def count_params(cfg: ModelConfig) -> Dict[str, float]:
    """Total/active/embedding parameter counts from the param shapes."""
    from repro.models import lm
    shapes = lm.param_shapes(cfg)
    total = active = embed = 0.0
    moe_frac = (cfg.moe.top_k / cfg.moe.num_experts) if cfg.moe else 1.0
    for path, leaf in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        names = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        n = 1.0
        for d in leaf.shape:
            n *= d
        total += n
        if "embed" in names:
            embed += n
            continue
        if ("ffn" in names and len(leaf.shape) >= 3 and cfg.moe
                and leaf.shape[-3] == cfg.moe.num_experts):
            active += n * moe_frac          # routed experts: top_k/E active
        else:
            active += n
    return {"total": total, "active": active, "embed": embed,
            "nonembed": total - embed}


def _attention_flops_per_token(cfg: ModelConfig, ctx: int) -> float:
    """Forward attention-score+value FLOPs per token at context ctx
    (averaged causal 1/2 factor; window layers use min(ctx, window))."""
    fl = 0.0
    for mixer, _ in layer_kinds(cfg):
        if mixer in ("attn", "xdec"):
            span = ctx / 2
        elif mixer == "local":
            span = min(ctx / 2, cfg.window)
        elif mixer == "mla":
            span = ctx / 2
        else:
            continue                        # ssd/rglru: linear, in params
        if cfg.mla is not None and mixer == "mla":
            h, dqk, dv = cfg.num_heads, (cfg.mla.qk_nope_head_dim +
                                         cfg.mla.qk_rope_head_dim), cfg.mla.v_head_dim
        else:
            h, dqk, dv = cfg.num_heads, cfg.head_dim, cfg.head_dim
        fl += 2 * span * h * (dqk + dv)
    return fl


def model_flops(cfg: ModelConfig, shape: ShapeConfig,
                bwd_fraction: float = 1.0) -> float:
    """Global useful FLOPs for one step of this cell.

    train: (2 + 4*bwd_fraction) * N_active * tokens + attention terms
    prefill: 2 * N_active * tokens + attention
    decode: 2 * N_active * batch + attention over the cache
    """
    n = count_params(cfg)["nonembed"]
    if cfg.moe:
        n = count_params(cfg)["active"]
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        tokens = B * S
        factor = 2 + 4 * bwd_fraction
        attn = _attention_flops_per_token(cfg, S) * tokens * (
            1 + 2 * bwd_fraction)
        return factor * n * tokens + attn
    if shape.kind == "prefill":
        tokens = B * S
        return 2 * n * tokens + _attention_flops_per_token(cfg, S) * tokens
    # decode: one token per sequence, attention over full cache
    attn_tok = 0.0
    for mixer, _ in layer_kinds(cfg):
        if mixer in ("attn", "xdec", "mla"):
            span = S
        elif mixer == "local":
            span = min(S, cfg.window)
        else:
            continue
        if cfg.mla is not None and mixer == "mla":
            # absorbed decode: scores/values in latent space of rank r
            span_cost = 2 * span * cfg.num_heads * (
                cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
                + cfg.mla.kv_lora_rank)
        else:
            span_cost = 2 * span * cfg.num_heads * 2 * cfg.head_dim
        attn_tok += span_cost
    return 2 * n * B + attn_tok * B


# ---------------------------------------------------------------------------
# Decode-phase serving roofline (bandwidth-bound tokens/s ceiling)
# ---------------------------------------------------------------------------

def _elem_bytes(cfg: ModelConfig) -> int:
    return 2 if cfg.dtype in ("bfloat16", "float16") else 4


def decode_kv_bytes(cfg: ModelConfig, context: int) -> float:
    """Bytes of KV cache ONE slot streams per decode step at ``context``.

    attn layers read the full context, local layers at most the window,
    MLA layers the latent (ckv + rope-k) rows; recurrent mixers carry
    O(1) state and are negligible here."""
    elem = _elem_bytes(cfg)
    total = 0.0
    for mixer, _ in layer_kinds(cfg):
        if mixer == "mla":
            total += context * (cfg.mla.kv_lora_rank
                                + cfg.mla.qk_rope_head_dim) * elem
            continue
        if mixer in ("attn", "xdec"):
            span = context
        elif mixer == "local":
            span = min(context, cfg.window)
        else:
            continue
        total += span * 2 * cfg.num_kv_heads * cfg.head_dim * elem
    return total


def decode_bandwidth_bound(cfg: ModelConfig, batch: int, context: int, *,
                           bw: float) -> float:
    """Bandwidth-bound decode throughput ceiling in tokens/s.

    Each decode step streams the (active) weights once — amortized over
    the whole batch, which is why continuous batching pays — plus every
    slot's KV context:

        tokens/s <= batch * BW / (weight_bytes + batch * kv_bytes(ctx))

    The weight term uses active params (MoE: top_k/E of the experts)
    plus the embedding/unembedding matrix, all in the model dtype.  This
    is the serving lane's analogue of the training roofline above: the
    measured BENCH_serve.json numbers report their distance to it.
    """
    counts = count_params(cfg)
    wbytes = (counts["active"] + counts["embed"]) * _elem_bytes(cfg)
    kv = decode_kv_bytes(cfg, context)
    return batch * bw / (wbytes + batch * kv)


# ---------------------------------------------------------------------------
# Pipeline-parallel terms (schedule-table driven)
# ---------------------------------------------------------------------------

def pipeline_bubble_fraction(num_stages: int, num_microbatches: int, *,
                             kind: str = "1f1b",
                             bwd_stages: Optional[int] = None,
                             bwd_cost: float = 2.0) -> float:
    """Idle fraction of a pipeline schedule, measured on its work table.

    Builds the actual (stage, microbatch, fwd/bwd) tick table —
    ``repro.dist.pipeline.schedules`` — and counts idle device-time
    slots, weighting backward ticks by ``bwd_cost``.  This replaces the
    GPipe-only closed form ``(S-1)/(M+S-1)`` (which the table reproduces
    exactly for a uniform-cost GPipe phase) and extends to 1F1B and the
    SPB-truncated schedules, whose frozen-prefix stages drain early.
    """
    from repro.dist.pipeline import schedules
    sched = schedules.build(kind, num_stages, num_microbatches,
                            bwd_stages=bwd_stages)
    return schedules.bubble_fraction_of(sched, bwd_cost=bwd_cost)


def pipeline_step_time(step_s: float, num_stages: int,
                       num_microbatches: int, *, kind: str = "1f1b",
                       bwd_stages: Optional[int] = None,
                       bwd_cost: float = 2.0) -> float:
    """Roofline step time under pipeline parallelism: the per-stage share
    of the non-pipelined step, inflated by the schedule's bubble."""
    bubble = pipeline_bubble_fraction(num_stages, num_microbatches,
                                      kind=kind, bwd_stages=bwd_stages,
                                      bwd_cost=bwd_cost)
    return (step_s / num_stages) / max(1.0 - bubble, 1e-9)


def pipeline_stash_watermark(num_stages: int, num_microbatches: int, *,
                             kind: str = "1f1b",
                             bwd_stages: Optional[int] = None,
                             sched=None) -> Tuple[int, int]:
    """(activation, cotangent) stash slots the schedule's runtime
    allocates — the per-stage memory watermark from the table's
    :func:`~repro.dist.pipeline.schedules.stash_plan`.  1F1B holds at
    most ``max_in_flight`` (≤ S, shrinking with SPB truncation) where
    GPipe holds all M of each.  Pass an already-built ``sched`` (e.g. a
    hand-edited table) to measure exactly it instead of rebuilding from
    ``(kind, bwd_stages)``."""
    from repro.dist.pipeline import schedules
    if sched is None:
        sched = schedules.build(kind, num_stages, num_microbatches,
                                bwd_stages=bwd_stages)
    elif (sched.num_stages, sched.num_microbatches) != \
            (num_stages, num_microbatches):
        raise ValueError(
            f"sched is {sched.num_stages}x{sched.num_microbatches} but the "
            f"arguments claim {num_stages}x{num_microbatches}")
    plan = schedules.stash_plan(sched)
    return plan.act_slots, plan.cot_slots


def pipeline_stash_bytes(cfg: ModelConfig, microbatch: int, seq_len: int,
                         num_stages: int, num_microbatches: int, *,
                         kind: str = "1f1b",
                         bwd_stages: Optional[int] = None,
                         data_parallel: int = 1, sched=None) -> int:
    """Bytes of activation+cotangent stash per device for one schedule —
    the quantity that separates 1F1B from GPipe in memory (and that SPB
    truncation shrinks further).  ``microbatch`` is the per-microbatch
    batch size *before* data sharding; each boundary activation is
    ``(microbatch / data_parallel, seq, d_model)`` in the model dtype."""
    act, cot = pipeline_stash_watermark(num_stages, num_microbatches,
                                        kind=kind, bwd_stages=bwd_stages,
                                        sched=sched)
    if data_parallel < 1 or microbatch % data_parallel:
        # keep the analysis honest: the runtime rejects these shapes too
        raise ValueError(f"microbatch size {microbatch} not divisible by "
                         f"data_parallel={data_parallel}")
    elem = 2 if cfg.dtype in ("bfloat16", "float16") else 4
    per_slot = (microbatch // data_parallel) * seq_len * cfg.d_model * elem
    return (act + cot) * per_slot


def pipeline_tp_collective_bytes(cfg: ModelConfig, microbatch: int,
                                 seq_len: int, num_stages: int,
                                 num_microbatches: int, *,
                                 model_parallel: int,
                                 data_parallel: int = 1,
                                 bwd_stages: Optional[int] = None,
                                 sequence_parallel: bool = False) -> float:
    """Per-device wire bytes of the in-stage tensor-parallel collectives
    for one pipeline step — the traffic the explicit Megatron joins add
    on top of the stage-boundary permutes.

    Each transformer layer has two joins (attention-out, MLP-down).  A
    join moves one residual-stream activation ``(mb/dp, seq, d_model)``:
    an all-reduce (ring wire ``2(n-1)/n * act``) in the replicated-
    activation layout, or an all-gather + reduce-scatter pair under
    sequence parallelism — the same wire bytes, so the join term is
    layout-independent.  The backward pass mirrors every join, so a
    stage whose backward SPB truncation freezes (``bwd_stages``) pays
    the forward half only.  Sequence parallelism adds the stage
    inlet/outlet transitions: one all-gather of the stream per
    microbatch at the outlet (forward) and the mirrored gather of the
    adjoint at the inlet when the stage runs backward.
    """
    n = int(model_parallel)
    if n <= 1:
        return 0.0
    if data_parallel < 1 or microbatch % data_parallel:
        raise ValueError(f"microbatch size {microbatch} not divisible by "
                         f"data_parallel={data_parallel}")
    elem = 2 if cfg.dtype in ("bfloat16", "float16") else 4
    act = (microbatch // data_parallel) * seq_len * cfg.d_model * elem
    try:
        from repro.config import stage_layer_counts
        # heterogeneous stage maps: the busiest stage bounds the wire
        layers_per_stage = max(1, max(stage_layer_counts(cfg, num_stages)))
    except (ValueError, ImportError):
        layers_per_stage = max(1, cfg.num_layers // max(num_stages, 1))
    bwd = num_stages if bwd_stages is None else max(0, min(bwd_stages,
                                                           num_stages))
    # per-device step totals, averaged over stages (bwd truncation only
    # spares the frozen stages; the deepest stage always pays both)
    wire_join = 2.0 * (n - 1) / n * act
    joins = 2 * layers_per_stage * num_microbatches
    fwd_total = joins * wire_join
    bwd_total = joins * wire_join * (bwd / max(num_stages, 1))
    total = fwd_total + bwd_total
    if sequence_parallel:
        edge = (n - 1) / n * act
        total += num_microbatches * edge                      # outlet gather
        total += num_microbatches * edge * (bwd / max(num_stages, 1))
    return total


# ---------------------------------------------------------------------------
# Roofline table
# ---------------------------------------------------------------------------

@dataclass
class RooflineRow:
    arch: str
    shape: str
    mesh: str
    chips: int
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops_global: float
    useful_ratio: float
    step_s: float                 # max of the three terms
    mfu: float                    # model_flops / (chips * peak * step_s)
    temp_gib: float

    @property
    def bound(self) -> str:
        return self.dominant


def load_record(arch: str, shape: str, mesh: str = "pod16x16",
                depth=None, tag: str = "") -> Optional[dict]:
    d = f"__d{depth}" if depth is not None else ""
    t = f"__{tag}" if tag else ""
    p = RESULTS / f"{arch}__{shape}__{mesh}{d}{t}.json"
    if not p.exists():
        return None
    rec = json.loads(p.read_text())
    return rec if rec.get("ok") else None


def roofline_row(rec: dict, cfg: ModelConfig,
                 device_kind: str) -> RooflineRow:
    shape = SHAPES[rec["shape"]]
    chips = rec["chips"]
    pk = peaks(device_kind)
    comp = rec["flops_per_device"] / pk.flops
    mem = rec["bytes_per_device"] / pk.hbm_bw
    coll = rec["collective_bytes_per_device"] / pk.link_bw
    terms = {"compute": comp, "memory": mem, "collective": coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    hlo_global = rec["flops_per_device"] * chips
    step = max(terms.values())
    mfu = mf / (chips * pk.flops * step) if step > 0 else 0.0
    temp = rec.get("memory_analysis", {}).get("temp_size_in_bytes", 0) / 2 ** 30
    return RooflineRow(
        arch=rec["arch"], shape=rec["shape"], mesh=rec["mesh"], chips=chips,
        compute_s=comp, memory_s=mem, collective_s=coll, dominant=dominant,
        model_flops=mf, hlo_flops_global=hlo_global,
        useful_ratio=mf / hlo_global if hlo_global else 0.0,
        step_s=step, mfu=mfu, temp_gib=temp)


def full_table(device_kind: str, mesh: str = "pod16x16"
               ) -> List[RooflineRow]:
    from repro.configs import cells, get_config
    rows = []
    for arch, shape, skip in cells():
        rec = load_record(arch, shape, mesh)
        if rec:
            rows.append(roofline_row(rec, get_config(arch), device_kind))
    return rows


def format_table(rows: List[RooflineRow]) -> str:
    hdr = (f"{'arch':24s} {'shape':12s} {'chips':>5s} {'compute':>9s} "
           f"{'memory':>9s} {'collectv':>9s} {'bound':>10s} {'MFU':>6s} "
           f"{'useful':>7s} {'temp':>8s}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        lines.append(
            f"{r.arch:24s} {r.shape:12s} {r.chips:5d} {r.compute_s:9.4f} "
            f"{r.memory_s:9.4f} {r.collective_s:9.4f} {r.dominant:>10s} "
            f"{r.mfu:6.1%} {r.useful_ratio:7.2f} {r.temp_gib:7.2f}G")
    return "\n".join(lines)


if __name__ == "__main__":
    print(format_table(full_table(DRYRUN_DEVICE_KIND)))
