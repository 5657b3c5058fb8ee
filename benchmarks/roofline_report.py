"""Roofline analysis (deliverable g) from the dry-run JSON cache.

Per (arch x shape), single-pod mesh (256 chips):

  compute term    = exact FLOPs/device   / 197 TF/s   (bf16 peak, v5e)
  memory term     = exact bytes/device   / 819 GB/s   (HBM)
  collective term = wire bytes/device    / 50 GB/s    (ICI per link)

"exact" FLOPs/bytes come from the layer-ladder cost analysis (XLA counts
scan bodies once; the ladder recovers per-layer cost — see
repro.models.registry.Arch.ladder).  Wire bytes come from the HLO collective
parser with while-loop trip multipliers.  MODEL_FLOPS = 6*N_active*D (train)
or 2*N_active*D (inference) gives the useful-compute ratio.

Upper-bound MFU ("roofline fraction", assuming perfect overlap):
  frac = compute_term / max(compute, memory, collective)
"""

from __future__ import annotations

import json
from pathlib import Path

from repro.core.params import TPU_V5E
from repro.models import registry

from benchmarks.run import register_benchmark

RESULTS_DIR = Path(__file__).resolve().parents[1] / "results" / "dryrun"
CHIPS = {"single": 256, "multi": 512}


def active_params(arch_name: str) -> float:
    """Parameters touched per token (MoE: shared + top-k routed only)."""
    arch = registry.get(arch_name)
    cfg = arch.config
    defs = arch.param_defs(cfg)
    total = 0

    def walk(node):
        nonlocal total
        if isinstance(node, dict):
            for v in node.values():
                walk(v)
        else:
            n = 1
            for s in node.shape:
                n *= s
            total += n

    walk(defs)
    if cfg.num_experts:
        # routed expert params: stacked wi (E,d,2f) + wo (E,f,d) per MoE layer
        d, f, e, k = (
            cfg.d_model,
            cfg.moe_hidden,
            cfg.num_experts,
            cfg.num_experts_per_tok,
        )
        n_moe_layers = cfg.num_layers - cfg.first_dense_layers
        routed = n_moe_layers * e * 3 * d * f
        total -= routed * (1.0 - k / e)
    return float(total)


def model_flops(arch_name: str, shape_name: str) -> float:
    shape = registry.SHAPES[shape_name]
    p_act = active_params(arch_name)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * p_act * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * p_act * tokens
    return 2.0 * p_act * shape.global_batch  # decode: one token per sequence


def load_cell(arch: str, shape: str, mesh: str, variant: str = "base") -> dict | None:
    safe = arch.replace("/", "_").replace(".", "_")
    p = RESULTS_DIR / f"{safe}__{shape}__{mesh}__{variant}.json"
    if not p.exists():
        return None
    return json.loads(p.read_text())


def roofline_terms(cell: dict) -> dict | None:
    if not cell.get("ok") or cell.get("skipped"):
        return None
    flops = cell.get("flops_per_device_exact") or cell.get("hlo_flops_per_device")
    # memory term: fusion-optimal dot traffic (TPU-realistic); XLA-CPU's raw
    # unfused 'bytes accessed' is reported alongside as the pessimistic bound.
    byts = cell.get("dot_bytes_per_device_exact")
    raw_bytes = cell.get("bytes_per_device_exact") or cell.get("hlo_bytes_per_device")
    if byts is None:
        byts = raw_bytes
    wire = cell.get("total_wire_bytes", 0.0)
    if flops is None or byts is None:
        return None
    t_c = flops / TPU_V5E.peak_flops_bf16
    t_m = byts / TPU_V5E.hbm_bandwidth
    t_x = wire / TPU_V5E.ici_bandwidth
    credit = flash_credit(cell["arch"], cell["shape"], cell["mesh"])
    t_m_flash = max(byts - credit, 0.0) / TPU_V5E.hbm_bandwidth
    dom = max(
        ("compute", t_c), ("memory", t_m), ("collective", t_x), key=lambda kv: kv[1]
    )
    bound_flash = max(t_c, t_m_flash, t_x)
    mf = model_flops(cell["arch"], cell["shape"]) / CHIPS[cell["mesh"]]
    return {
        "compute_s": t_c,
        "memory_s": t_m,
        "memory_s_flash": t_m_flash,
        "memory_s_pessimistic": (raw_bytes or 0.0) / TPU_V5E.hbm_bandwidth,
        "collective_s": t_x,
        "dominant": dom[0],
        "bound_s": dom[1],
        "mfu_upper_bound": t_c / dom[1] if dom[1] > 0 else 0.0,
        "mfu_ub_flash": t_c / bound_flash if bound_flash > 0 else 0.0,
        "model_flops_per_device": mf,
        "useful_ratio": mf / flops if flops else 0.0,
    }


def flash_credit(arch_name: str, shape_name: str, mesh: str) -> float:
    """Removable attention-score HBM traffic per device, assuming the
    flash-attention Pallas kernel (kernels/flash_attention) replaces the
    scanned implementation: score/probability matrices stay in VMEM.

    Dot-parser accounting of the as-written model counts ~8x the score
    matrix for train (s out + p in, x2 for remat recompute, + ds/dp in
    backward) and 2x for prefill; scores are f32 as compiled.
    """
    arch = registry.get(arch_name)
    cfg = arch.config.pad_for_mesh(16)
    shape = registry.SHAPES[shape_name]
    if shape.kind == "decode":
        return 0.0
    data_ax = CHIPS[mesh] // 16
    b_dev = max(shape.global_batch // data_ax, 1)
    h_dev = max(cfg.n_q_heads // 16, 1)
    factor = 8.0 if shape.kind == "train" else 2.0
    t = shape.seq_len

    def score_bytes(layers, tq, tk, heads_dev):
        return layers * b_dev * heads_dev * tq * tk * 4.0

    fam = arch.family
    if fam in ("dense", "moe"):
        return factor * score_bytes(cfg.num_layers, t, t, h_dev)
    if fam == "vlm":
        n_cross = cfg.num_layers // cfg.cross_attn_every
        n_self = cfg.num_layers - n_cross
        return factor * (
            score_bytes(n_self, t, t, h_dev)
            + score_bytes(n_cross, t, cfg.vision_seq, h_dev)
        )
    if fam == "audio":
        td = t // cfg.decoder_ratio
        return factor * (
            score_bytes(cfg.encoder_layers, t, t, h_dev)      # encoder self
            + score_bytes(cfg.num_layers, td, td, h_dev)      # decoder self
            + score_bytes(cfg.num_layers, td, t, h_dev)       # cross
        )
    if fam == "ssm":  # mLSTM chunkwise scores (T x chunk), heads replicated
        n_m = cfg.num_layers - cfg.num_layers // cfg.slstm_every
        return factor * score_bytes(n_m, t, cfg.ssm_chunk, cfg.num_heads)
    if fam == "hybrid":  # SSD chunk scores + shared attn invocations
        n_groups = cfg.num_layers // cfg.attn_every
        ssd = score_bytes(
            cfg.num_layers, t, cfg.ssm_chunk, 1
        )  # (C.B) per head pair-free
        attn_b = score_bytes(n_groups, t, t, h_dev)
        return factor * (ssd + attn_b)
    return 0.0


RECOMMEND = {
    "compute": "compute-bound: raise per-chip efficiency (fusion, int8/bf16 "
    "mix, photonic offload of weight GEMMs)",
    "memory": "HBM-bound: cut activation traffic (flash-attention kernel, "
    "chunked CE loss, wider remat, f32->bf16 intermediates)",
    "collective": "ICI-bound: reshard to cut all-gathers (SP residual), "
    "overlap collectives with compute, int8-compress gradients",
}


def render(write_experiments: bool = False) -> str:
    lines = []
    lines.append(
        "| arch | shape | FLOPs/dev | compute s | memory s | mem+flash s "
        "| collective s | dominant | MFU-UB | UB+flash | useful | note |"
    )
    lines.append("|---|---|---|---|---|---|---|---|---|---|---|---|")
    incomplete = 0
    for arch in registry.names():
        a = registry.get(arch)
        for shape in registry.SHAPES:
            cell = load_cell(arch, shape, "single")
            if cell is None:
                incomplete += 1
                continue
            if cell.get("skipped"):
                lines.append(
                    f"| {arch} | {shape} | — | — | — | — | — | skipped "
                    f"| — | — | — | {a.notes.split(';')[0][:40]} |"
                )
                continue
            t = roofline_terms(cell)
            if t is None:
                lines.append(f"| {arch} | {shape} | FAILED | | | | | | | | | |")
                continue
            lines.append(
                f"| {arch} | {shape} | {cell.get('flops_per_device_exact', 0)/1e12:.2f}T "
                f"| {t['compute_s']:.3g} | {t['memory_s']:.3g} | {t['memory_s_flash']:.3g} "
                f"| {t['collective_s']:.3g} "
                f"| {t['dominant']} | {t['mfu_upper_bound']:.2f} | {t['mfu_ub_flash']:.2f} "
                f"| {t['useful_ratio']:.2f} "
                f"| {RECOMMEND[t['dominant']][:48]} |"
            )
    table = "\n".join(lines)
    if incomplete:
        table += f"\n\n({incomplete} cells pending in the dry-run sweep)"
    return table


@register_benchmark("roofline_report")
def main(smoke=False):
    del smoke  # pure post-processing of cached dry-run JSON
    print("roofline_report,per_cell_terms")
    print(render())
    # summary stats for §Perf selection
    worst = None
    most_coll = None
    for arch in registry.names():
        for shape in registry.SHAPES:
            cell = load_cell(arch, shape, "single")
            if not cell or cell.get("skipped") or not cell.get("ok"):
                continue
            t = roofline_terms(cell)
            if t is None:
                continue
            if worst is None or t["mfu_upper_bound"] < worst[2]:
                worst = (arch, shape, t["mfu_upper_bound"])
            ratio = t["collective_s"] / max(t["bound_s"], 1e-30)
            if most_coll is None or ratio > most_coll[2]:
                most_coll = (arch, shape, ratio)
    if worst:
        print(f"# worst_mfu_ub={worst}")
    if most_coll:
        print(f"# most_collective_bound={most_coll}")
    return {
        "worst_mfu_upper_bound": list(worst) if worst else None,
        "most_collective_bound": list(most_coll) if most_coll else None,
    }


if __name__ == "__main__":
    main()
