"""Three-term roofline analysis from a compiled (dry-run) executable.

    compute_s    = HLO_FLOPs / (chips × 197 TFLOP/s)
    memory_s     = HLO_bytes / (chips × 819 GB/s)
    collective_s = collective_bytes / (chips × 50 GB/s per ICI link)

``cost_analysis`` supplies FLOPs and bytes; collective bytes are NOT in
cost_analysis, so we parse the post-SPMD optimized HLO (``compiled.as_text()``)
and sum operand/result sizes of every all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute. Post-partitioning HLO shapes are PER-DEVICE, so
the parsed bytes are per-chip wire bytes (ring-factor approximations noted
per-op below); cost_analysis of a partitioned module is likewise per-device.
"""
from __future__ import annotations

import re
from dataclasses import asdict, dataclass, field
from typing import Optional

# the dry-run targets a v5e pod: the v5e row of the per-device peak table,
# shared with core/protocol.py so latency estimates can't diverge from it
from repro.hw import V5E

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLL_RE = re.compile(
    r"^\s*(?:%|)(?P<name>[\w.\-]*)\s*=\s*(?P<rshape>[^=]*?)\s+"
    r"(?P<op>all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
    re.M,
)

_SHAPE_RE = re.compile(r"(?P<dt>\w+\d*)\[(?P<dims>[\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Sum bytes over (possibly tuple) shape strings like '(bf16[8,128], f32[4])'."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt = m.group("dt")
        if dt not in _DTYPE_BYTES:
            continue
        dims = m.group("dims")
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


# Wire-cost multiplier per collective (ring algorithm, n→∞ limit):
#   all-reduce = 2× payload (reduce-scatter + all-gather phases)
#   all-gather / reduce-scatter / all-to-all / collective-permute ≈ 1× payload
_OP_FACTOR = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


@dataclass
class CollectiveStats:
    counts: dict = field(default_factory=dict)
    bytes_by_op: dict = field(default_factory=dict)
    total_bytes: float = 0.0  # wire bytes per device (factor-weighted)


def parse_collectives(hlo_text: str) -> CollectiveStats:
    st = CollectiveStats()
    for m in _COLL_RE.finditer(hlo_text):
        op = m.group("op")
        b = _shape_bytes(m.group("rshape"))
        st.counts[op] = st.counts.get(op, 0) + 1
        st.bytes_by_op[op] = st.bytes_by_op.get(op, 0) + b
        st.total_bytes += b * _OP_FACTOR[op]
    return st


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    analytic_flops: float  # global, all chips (closed-form; see flops_analytic)
    hlo_flops_raw: float  # per device, uncorrected cost_analysis (scan bodies ×1)
    hlo_bytes: float  # per device, cycle-extrapolated
    hlo_bytes_raw: float
    collective_bytes: float  # per device (wire, factor-weighted, extrapolated)
    collective_bytes_raw: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float  # 6·N(active)·D global
    useful_ratio: float  # model_flops / analytic_flops
    collectives: dict = field(default_factory=dict)
    memory_per_device: Optional[dict] = None

    def to_json(self) -> dict:
        return asdict(self)


def cost_of(compiled) -> dict:
    """(flops, bytes, collective wire bytes) of one compiled executable,
    per device, as reported (no loop correction)."""
    ca = compiled.cost_analysis()
    if isinstance(ca, list):  # some jax versions return [dict]
        ca = ca[0]
    coll = parse_collectives(compiled.as_text())
    return {
        "flops": float(ca.get("flops", 0.0)),
        "bytes": float(ca.get("bytes accessed", 0.0)),
        "coll_bytes": coll.total_bytes,
        "coll_counts": coll.counts,
        "coll_by_op": coll.bytes_by_op,
    }


def analyze(
    *,
    arch: str,
    shape_name: str,
    mesh_name: str,
    chips: int,
    compiled,
    model_flops: float,
    analytic_flops: float,
    bytes_corrected: Optional[float] = None,
    coll_corrected: Optional[float] = None,
    ici_links: int = 1,
) -> Roofline:
    raw = cost_of(compiled)
    bytes_ = bytes_corrected if bytes_corrected is not None else raw["bytes"]
    coll_b = coll_corrected if coll_corrected is not None else raw["coll_bytes"]

    compute_s = analytic_flops / (chips * V5E.bf16_flops)
    memory_s = bytes_ / V5E.hbm_bw
    collective_s = coll_b / (V5E.ici_bw * ici_links)
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    bottleneck = max(terms, key=terms.get)

    mem = None
    try:
        ma = compiled.memory_analysis()
        mem = {
            "argument_bytes": getattr(ma, "argument_size_in_bytes", None),
            "output_bytes": getattr(ma, "output_size_in_bytes", None),
            "temp_bytes": getattr(ma, "temp_size_in_bytes", None),
            "peak_bytes": getattr(ma, "peak_memory_in_bytes", None),
        }
    except Exception:
        pass

    return Roofline(
        arch=arch, shape=shape_name, mesh=mesh_name, chips=chips,
        analytic_flops=analytic_flops,
        hlo_flops_raw=raw["flops"],
        hlo_bytes=bytes_, hlo_bytes_raw=raw["bytes"],
        collective_bytes=coll_b, collective_bytes_raw=raw["coll_bytes"],
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bottleneck=bottleneck,
        model_flops=model_flops,
        useful_ratio=(model_flops / analytic_flops) if analytic_flops else 0.0,
        collectives={"counts": raw["coll_counts"], "bytes": raw["coll_by_op"]},
        memory_per_device=mem,
    )


def flops_analytic(cfg, shape, kind: str, *, remat: bool = True,
                   window_override: int = 0,
                   moe_group: int = 0, moe_cap: float = 0.0) -> float:
    """Exact closed-form FLOPs of the model AS WRITTEN (global, all chips).

    Why analytic: XLA's HLO cost analysis counts while-loop (scan) bodies ONCE,
    not × trip-count (verified empirically — see EXPERIMENTS.md §Dry-run), and
    both the layer scan and the flash-attention chunk scans are loops. We control
    every einsum in the model, so the closed form is exact; the raw
    cost_analysis numbers are reported alongside for transparency.

    Conventions: FLOPs = 2·MACs; flash attention computes full S per query
    (masked blocks included — that's the real chip work); train multiplier ×4 on
    layers (fwd 1, bwd 2, remat re-forward 1; ×3 without remat), ×3 on lm_head
    (never rematerialised).
    """
    B, S = shape.global_batch, shape.seq_len
    d, hd = cfg.d_model, cfg.resolved_head_dim
    H, Hkv = cfg.num_heads, cfg.num_kv_heads
    if kind == "train":
        tokens, s_att = B * S, S
    elif kind == "prefill":
        tokens, s_att = B * S, S
    else:  # decode: one token per sequence; attends over the whole cache
        tokens, s_att = B, S

    per_tok = 0.0
    win = window_override or cfg.sliding_window
    for t in cfg.layer_types:
        if t in ("attn", "swa"):
            s_eff = min(win, s_att) if (t == "swa" and win) else s_att
            per_tok += 2 * d * (2 * H * hd + 2 * Hkv * hd)  # qkvo projections
            per_tok += 2 * 2 * H * hd * s_eff  # scores + AV
            if cfg.num_experts:
                E, K, f = cfg.num_experts, cfg.num_experts_per_tok, cfg.moe_d_ff
                g = min(moe_group or cfg.moe_group_size, tokens)
                moe_cap = moe_cap or cfg.moe_capacity_factor
                C = min(max(int(g * K / E * moe_cap), 4) + 3 & ~3, g)
                per_tok += 2 * 3 * d * f * K  # routed experts
                per_tok += 2 * 2 * E * C * d  # dispatch + combine einsums
                per_tok += 2 * d * E  # router
                if cfg.num_shared_experts:
                    per_tok += 2 * 3 * d * (f * cfg.num_shared_experts)
            else:
                per_tok += 2 * 3 * d * cfg.d_ff
        elif t == "rec":
            W = cfg.rglru_width or d
            nh = max(cfg.num_heads, 1)
            per_tok += 2 * 2 * d * W + 2 * W * d  # in projs + out proj
            per_tok += 2 * cfg.conv_kernel * W
            per_tok += 2 * 2 * W * (W // nh)  # block-diagonal gates
            per_tok += 2 * 3 * d * cfg.d_ff
        elif t == "ssd":
            di, ns, ng = cfg.d_inner, cfg.ssm_state, cfg.ssm_ngroups
            nh, p = cfg.ssm_nheads, cfg.ssm_head_dim
            Q = min(128, s_att)
            per_tok += 2 * d * (2 * di + 2 * ng * ns + nh)  # in_proj
            per_tok += 2 * cfg.conv_kernel * (di + 2 * ng * ns)
            if kind == "decode":
                per_tok += 2 * nh * p * ns * 2  # state update + readout
            else:
                per_tok += 2 * Q * nh * ns + 2 * Q * nh * p  # intra-chunk
                per_tok += 2 * 2 * nh * p * ns  # states + off-diag
            per_tok += 2 * di * d  # out_proj
    head = 2 * d * cfg.vocab_size  # lm_head / tied unembed, per token

    if kind == "train":
        mult_layers = 4.0 if remat else 3.0
        return tokens * (per_tok * mult_layers + head * 3.0)
    return tokens * (per_tok + head)


def model_flops_for(cfg, shape, kind: str) -> float:
    """6·N·D (dense) / 6·N_active·D (MoE); decode counts D=1 new token/seq."""
    n = cfg.active_param_count()
    if kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch
