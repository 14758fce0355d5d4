"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch × shape × mesh):

  compute_s    = FLOPs / (chips × 197 TFLOP/s bf16)
  memory_s     = HBM bytes / (chips × 819 GB/s)
  collective_s = per-chip communicated bytes / (50 GB/s/link)

Sources and caveats (see EXPERIMENTS.md §Methodology):

* ``collective_bytes`` is parsed from the compiled SPMD HLO: every
  all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute,
  with ring-algorithm byte multipliers and **while-loop trip-count
  attribution** — XLA's cost_analysis counts a while body once, so each
  computation's contribution is multiplied by its loop trip count (parsed
  from the loop-condition constant), which matters because all layers live
  inside a `lax.scan`.
* compute/memory use exact analytic accounting from the model config
  (6·N·D weight FLOPs (+ attention/SSD terms), parameter+optimizer+activation
  HBM traffic).  Raw ``cost_analysis`` numbers are recorded alongside, with
  the loop-undercount caveat.
* The CPU backend legalizes some bf16 dots to f32, so ``memory_analysis``
  per-device bytes are up to ~2× pessimistic vs TPU for matmul-adjacent
  temporaries; raw values are reported as upper bounds.
"""

from __future__ import annotations

import dataclasses
import re

HW = {
    "peak_flops": 197e12,   # bf16 per chip (TPU v5e-class)
    "hbm_bw": 819e9,        # bytes/s per chip
    "link_bw": 50e9,        # bytes/s per ICI link
}


# -- measured-ops basis for the counting side (DESIGN.md §9) -------------------
#
# The cost-model subsystem (repro/costmodel/) fits per-(device, impl, kind)
# affine models  t ≈ a + b·ops  where ``ops`` is the job's work in this basis.
# The basis is deliberately the *horizontal* §3 form — candidate-word
# comparisons — for every impl: vertical/delta layouts do proportionally less
# word work, but the constant of proportionality is absorbed by the per-key
# slope ``b``, so the affine family is the same and fits never mix bases.
#
# Device→host transfers ride in the same basis: one PCIe byte is priced at
# ``XFER_OPS_PER_BYTE`` candidate-word comparisons (a ~10 GB/s link against a
# compute path that retires hundreds of Gops/s of word tests), so
# impl/fusion decisions see the transfer cost of the result shapes they
# produce, not only the counting work (PR 6 follow-on, DESIGN.md §10).

XFER_OPS_PER_BYTE = 64.0


def count_job_ops(n_candidates: int, n_txns: int, n_words: int = 1,
                  bytes_to_host: float = 0.0) -> float:
    """Work of one support-counting job in the measured-ops basis: C·T·W
    candidate-word comparisons (each of C candidates tested against each of
    T transactions over W mask words), plus the job's device→host result
    traffic priced at ``XFER_OPS_PER_BYTE`` ops per byte."""
    ops = float(max(int(n_candidates), 1)) * max(int(n_txns), 1) * \
        max(int(n_words), 1)
    return ops + max(float(bytes_to_host), 0.0) * XFER_OPS_PER_BYTE


# -- counting-kernel roofline (DESIGN.md §10) ----------------------------------
#
# Per-chip peaks for the achieved-vs-peak fractions, keyed by the device kind
# JAX reports (``jax.devices()[0].device_kind``).  The matmul (bit-plane
# dot) forms are compute-bound and are compared against the int8 matmul peak;
# the popcount forms stream words and are compared against HBM bandwidth.  A
# device that is not in the table has no roofline: asking for one raises.
#
# Source: Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
# 393 TOP/s int8, 16 GB of HBM at 819 GB/s per chip.

COUNT_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "mem_bw": 819e9},
}


def count_kernel_roofline(impl: str, *, C: int, T: int, W: int = 1,
                          kmax: int = 1, n_items: int | None = None,
                          seconds: float, device_kind: str,
                          chips: int = 1) -> dict:
    """Achieved-vs-peak terms for one counting job.

    Each counting form is priced by work its kernel cannot avoid — never
    more — so the share stays at or under 1 at the true peak:

    * the matmul forms: the ``(C, 32·W) × (32·W, T)`` bit-plane product of
      ``matmul`` / ``matmul_pallas``, the ``(C, I) × (I, T)`` membership
      product of ``vertical_matmul`` / ``vertical_matmul_pallas``, 2 int8
      ops per MAC (the kernels pad the contracted axis further);
    * ``jnp`` / ``pallas`` (horizontal popcount): the compulsory HBM traffic,
      every candidate and transaction word read once and one count written;
    * ``vertical`` (jnp gather): each candidate gathers its ``kmax`` item
      rows of ``T/32`` words;
    * ``vertical_pallas``: the kernel streams the whole ``(I+1, T/32)`` item
      array once per block of ``BC`` candidates, plus the index table; when
      the word axis fits one default block, the array is read once.

    Args:
      impl: a counting impl of ``core.mapreduce.IMPLS`` (no ``_interpret``).
      C/T/W/kmax: the job's shape (T = transaction rows, W = mask words).
      n_items: the catalog size I (vertical forms only; default 32·W).
      seconds: measured wall time of the job.
      device_kind: the chip's ``device_kind``; must be a key of
              ``COUNT_PEAKS``.
      chips: devices the job ran on — the whole job's work is compared
              against ``chips`` times the per-chip peak.

    Returns a dict with the achieved rate, the peak it is measured against,
    the ``peak_frac`` ratio, and which resource bounds the form.
    """
    if device_kind not in COUNT_PEAKS:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; "
            f"COUNT_PEAKS covers {sorted(COUNT_PEAKS)}")
    peaks = COUNT_PEAKS[device_kind]
    s = max(float(seconds), 1e-12)
    tw = -(-int(T) // 32)                    # transaction words
    items = 32 * W if n_items is None else n_items
    if "matmul" in impl:
        # the vertical forms contract over the I items, the horizontal over
        # the 32·W bit planes of the masks
        planes = items if impl.startswith("vertical") else 32 * W
        achieved = 2.0 * float(C) * T * planes / s
        peak, bound, unit = peaks["int8_ops"], "compute", "int8_ops_per_s"
    else:
        if impl == "vertical_pallas":
            from repro.kernels.vertical_count import BC, DEFAULT_BT
            # one word block stays resident across the candidate blocks
            bc = min(BC, -(-C // 8) * 8)
            fetches = -(-C // bc) if tw > DEFAULT_BT else 1
            words = fetches * (items + 1) * tw + C * kmax + C
        elif impl == "vertical":
            words = C * kmax * tw + C
        elif impl in ("jnp", "pallas"):
            words = W * (C + T) + C
        else:
            raise ValueError(f"no roofline model for impl {impl!r}")
        achieved = 4.0 * words / s
        peak, bound, unit = peaks["mem_bw"], "memory", "bytes_per_s"
    peak = float(peak) * chips
    return {"impl": impl, "bound": bound, "unit": unit,
            "achieved": float(achieved), "peak": peak,
            "peak_frac": float(achieved / peak)}


def predicted_vs_achieved(predicted_s: float, achieved_s: float) -> dict:
    """One predicted-vs-measured comparison row (cost-model telemetry)."""
    ratio = predicted_s / achieved_s if achieved_s > 0 else float("inf")
    rel_err = (abs(predicted_s - achieved_s) / achieved_s
               if achieved_s > 0 else float("inf"))
    return {"predicted_s": float(predicted_s), "achieved_s": float(achieved_s),
            "ratio": float(ratio), "abs_rel_err": float(rel_err)}

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLL_RE = re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s+\([^)]*\)\s*->", re.M)
_CALL_RE = re.compile(
    r"(?:body=|condition=|calls=|to_apply=|branch_computations=\{)\s*%?([\w\.\-]+)")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def _split_computations(hlo: str) -> dict:
    """Split HLO text into {computation_name: body_text}."""
    comps = {}
    cur_name, cur_lines = None, []
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY\s+)?%?([\w\.\-]+)\s+\([^)]*\)\s*->.*\{", line)
        if m:
            if cur_name:
                comps[cur_name] = "\n".join(cur_lines)
            cur_name, cur_lines = m.group(1), [line]
        elif cur_name is not None:
            cur_lines.append(line)
            if line.startswith("}"):
                comps[cur_name] = "\n".join(cur_lines)
                cur_name, cur_lines = None, []
    if cur_name:
        comps[cur_name] = "\n".join(cur_lines)
    return comps


def _trip_count(cond_text: str) -> int:
    """Heuristic scan trip count: the largest s32 constant in the condition."""
    consts = [int(c) for c in re.findall(r"constant\((\d+)\)", cond_text)]
    return max(consts) if consts else 1


def _group_size(line: str, n_devices: int) -> int:
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    m = re.search(r"replica_groups=\{\{([\d,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    return n_devices


def _comm_factor(op: str, g: int) -> float:
    """Per-chip communicated bytes as a multiple of the tensor bytes (ring)."""
    if g <= 1:
        return 0.0
    if op == "all-gather":
        return (g - 1) / g
    if op == "all-reduce":
        return 2 * (g - 1) / g
    if op == "reduce-scatter":
        return (g - 1) / g
    if op == "all-to-all":
        return (g - 1) / g
    if op == "collective-permute":
        return 1.0
    return 1.0


def parse_collectives(hlo: str, n_devices: int) -> dict:
    """Per-chip communicated bytes by collective op, trip-count weighted."""
    comps = _split_computations(hlo)

    # computation multipliers: ENTRY ×1; while bodies × trip count
    mult = {}
    entry = None
    for name in comps:
        if re.search(rf"^ENTRY\s+%?{re.escape(name)}\b", hlo, re.M):
            entry = name
    order = [(entry or next(iter(comps)), 1.0)]
    seen = set()
    while order:
        name, m = order.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        mult[name] = mult.get(name, 0.0) + m
        body = comps[name]
        # while ops: body gets ×trip, condition ×trip
        for wm in re.finditer(
                r"while\(.*?condition=%?([\w\.\-]+),\s*body=%?([\w\.\-]+)", body):
            cond, wbody = wm.group(1), wm.group(2)
            trip = _trip_count(comps.get(cond, ""))
            order.append((wbody, m * trip))
            order.append((cond, m * trip))
        for cm in _CALL_RE.finditer(body):
            callee = cm.group(1)
            if callee not in seen and not body.count(f"body=%{callee}"):
                order.append((callee, m))

    by_op: dict = {}
    total = 0.0
    for name, m in mult.items():
        for line in comps[name].splitlines():
            cm = _COLL_RE.search(line)
            if not cm:
                continue
            shape_str, op = cm.group(1), cm.group(2)
            b = _shape_bytes(shape_str)
            g = _group_size(line, n_devices)
            comm = b * _comm_factor(op, g) * m
            by_op[op] = by_op.get(op, 0.0) + comm
            total += comm
    return {"by_op": by_op, "per_chip_bytes": total}


# -- analytic FLOPs / bytes ----------------------------------------------------

def analytic_flops(cfg, shape) -> dict:
    """Exact-form FLOP accounting for one step of the given kind."""
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    n_active = cfg.active_param_count()
    n_total = cfg.param_count()
    weight_flops_fwd = 2 * n_active * tokens

    # attention: 2·S_ctx·hd FLOPs per (token, head) for qk plus same for pv
    hd = cfg.resolved_head_dim
    n_attn_layers = sum(1 for i in range(cfg.n_layers) if cfg.layer_kind(i) == "attn")
    n_attn_layers += cfg.n_encoder_layers
    if shape.kind == "decode":
        ctx_len = shape.seq_len
        attn_fwd = 4 * ctx_len * cfg.padded_heads * hd * n_attn_layers * shape.global_batch
    else:
        ctx_avg = shape.seq_len / 2
        attn_fwd = 4 * ctx_avg * cfg.padded_heads * hd * n_attn_layers * tokens

    # SSD: per token·head: intra-chunk ≈ 2·L·(N + hd) + state update 2·N·hd
    ssd_fwd = 0
    if cfg.ssm_state:
        from repro.models.ssm import ssm_dims
        d_inner, H, Pd, N = ssm_dims(cfg)
        n_ssm = sum(1 for i in range(cfg.n_layers) if cfg.layer_kind(i) == "ssm")
        if shape.kind == "decode":
            ssd_fwd = 2 * H * Pd * N * 2 * n_ssm * shape.global_batch
        else:
            L = 256
            ssd_fwd = (2 * L * (N + Pd) + 4 * N * Pd) * H * n_ssm * tokens

    fwd = weight_flops_fwd + attn_fwd + ssd_fwd
    if shape.kind == "train":
        total = 3 * fwd          # bwd ≈ 2× fwd
        # remat recompute: full policy re-runs the forward; "dots" saves
        # matmul outputs and only recomputes elementwise glue (~15%)
        total += fwd if getattr(cfg, "remat_policy", "full") == "full" else 0.15 * fwd
        model_flops = 6 * n_active * tokens
    else:
        total = fwd
        model_flops = 2 * n_active * tokens
    return {"model_flops": float(model_flops), "total_flops": float(total),
            "fwd_flops": float(fwd), "tokens": tokens,
            "params_total": n_total, "params_active": n_active}


def analytic_bytes(cfg, shape, chips: int) -> float:
    """Per-step global HBM traffic (bytes), all chips combined."""
    n = cfg.param_count()
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
    act_unit = tokens * cfg.d_model * 2  # bf16 residual
    layers = cfg.n_layers + cfg.n_encoder_layers
    if shape.kind == "train":
        # params read (fwd+bwd+remat) ×3, grads written, opt m/v read+write f32,
        # master update; remat-saved activations written+read
        weight_traffic = n * 2 * 3 + n * 2 + 4 * n * 4
        act_traffic = act_unit * layers * (2 + 10)  # saves + working set approx
        return float(weight_traffic + act_traffic)
    if shape.kind == "prefill":
        weight_traffic = n * 2
        act_traffic = act_unit * layers * 6
        return float(weight_traffic + act_traffic)
    # decode: whole weight set + KV cache read per token step
    hd = cfg.resolved_head_dim
    n_attn = sum(1 for i in range(cfg.n_layers) if cfg.layer_kind(i) == "attn")
    kv_bytes = (2 * shape.seq_len * cfg.n_kv_heads * hd * n_attn
                * shape.global_batch * 2)
    return float(cfg.active_param_count() * 2 + kv_bytes)


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float
    hlo_flops_raw: float
    useful_ratio: float

    def as_dict(self):
        return dataclasses.asdict(self)


def roofline_terms(cfg, shape, chips: int, collective_per_chip_bytes: float,
                   hlo_flops_raw: float = 0.0) -> RooflineTerms:
    fl = analytic_flops(cfg, shape)
    by = analytic_bytes(cfg, shape, chips)
    compute_s = fl["total_flops"] / (chips * HW["peak_flops"])
    memory_s = by / (chips * HW["hbm_bw"])
    collective_s = collective_per_chip_bytes / HW["link_bw"]
    terms = {"compute": compute_s, "memory": memory_s, "collective": collective_s}
    dominant = max(terms, key=terms.get)
    useful = fl["model_flops"] / fl["total_flops"] if fl["total_flops"] else 0.0
    return RooflineTerms(compute_s, memory_s, collective_s, dominant,
                         fl["model_flops"], hlo_flops_raw, useful)
