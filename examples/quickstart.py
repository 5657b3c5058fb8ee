"""Quickstart: the photonic DPU GEMM in five minutes.

1. Ask the scalability model (paper Eq.1-3) what DPE size N each
   organization supports at your precision/datarate.
2. Build a DPUConfig and run a GEMM through the photonic datapath.
3. Compare against the exact result; flip organizations and noise.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import scalability as sc
from repro.core.dpu import DPUConfig, noise_sigma_from_snr
from repro.noise import build_channel_model
from repro.photonic import engine_for, pack_dense


def main():
    from repro.launch import profile

    profile.apply()  # tuned launch env + persistent compilation cache
    print("=== 1. scalability: achievable DPE size N (=M) ===")
    for org in ("ASMW", "MASW", "SMWA"):
        ns = [sc.calibrated_max_n(org, 4, dr) for dr in (1, 5, 10)]
        print(f"  {org}: N @ {{1,5,10}} GS/s = {ns}   (paper Table V: "
              f"{[sc.TABLE_V_N[(org, d)] for d in (1, 5, 10)]})")

    print("\n=== 2. GEMM through the SMWA DPU datapath ===")
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(64, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    exact = x @ w

    cfg = DPUConfig(organization="SMWA", bits=4, datarate_gs=5.0)
    print(
        f"  operating point: N={cfg.n}, M={cfg.m}, "
        f"{cfg.num_slices} slices x {cfg.num_slices} = {cfg.passes} passes, "
        f"{cfg.num_chunks(256)} psum chunks for k=256"
    )
    y = engine_for(cfg, "ref").matmul_float(x, w)
    rel = float(jnp.linalg.norm(y - exact) / jnp.linalg.norm(exact))
    print(f"  ideal datapath rel-error vs float GEMM: {rel:.4f} (int8 quantization)")

    y_pallas = engine_for(cfg, "pallas").matmul_float(x, w)  # interpret on CPU
    print(f"  pallas kernel == ref: {bool(jnp.allclose(y_pallas, y))}")

    print("\n=== 3. analog noise at the scalability budget ===")
    for mult in (1.0, 4.0):
        sigma = mult * noise_sigma_from_snr(cfg)
        ncfg = DPUConfig(
            organization="SMWA", bits=4, datarate_gs=5.0, noise_sigma_lsb=sigma
        )
        yn = engine_for(ncfg, "ref").matmul_float(
            x, w, prng_key=jax.random.PRNGKey(0)
        )
        rel = float(jnp.linalg.norm(yn - exact) / jnp.linalg.norm(exact))
        print(
            f"  noise {mult:>3.0f}x budget (sigma={sigma:.1f} LSB): rel-error {rel:.4f}"
        )

    print("\n=== 4. the organization-aware channel model (repro.noise) ===")
    for org in ("ASMW", "MASW", "SMWA"):
        ch = build_channel_model(org, n=17, bits=4, datarate_gs=5.0)
        ocfg = DPUConfig(
            organization=org, bits=4, dpe_size=17, channel=ch, noise_seed=0
        )
        yo = engine_for(ocfg, "ref").matmul_float(x, w)
        rel = float(jnp.linalg.norm(yo - exact) / jnp.linalg.norm(exact))
        print(
            f"  {org}: through-loss {ch.through_loss_db:.2f} dB, "
            f"sigma {ch.detector_sigma_lsb:.1f} LSB, "
            f"xtalk (im/cw/filt) = ({ch.intermod_eps:.3f}/"
            f"{ch.crossweight_eps:.3f}/{ch.filter_alpha:.3f}) "
            f"-> rel-error {rel:.4f}"
        )

    print("\n=== 5. the execution engine: prepacked weight-stationary GEMM ===")
    eng = engine_for(cfg, "ref")
    print(f"  {eng.describe()}")
    packed = pack_dense({"w": w}, eng)["w"]
    y_pack = eng.matmul(x, packed, site="demo")
    y_call = eng.matmul_float(x, w, site="demo")
    print(
        f"  prepacked == per-call quantization: "
        f"{bool(jnp.array_equal(y_pack, y_call))}  ({packed})"
    )
    print(
        "  routing policy: "
        f"routes('ffn.wi')={eng.routes('ffn.wi')}, "
        f"routes('ffn.router')={eng.routes('ffn.router')} "
        "(MoE routing stays digital by default)"
    )


if __name__ == "__main__":
    main()
