"""Per-organization end-to-end accuracy vs DPE size N (paper §V-B claim,
quantified through `repro.noise`).

The paper asserts "minimal or no loss in inference accuracy" for prior
photonic GEMM accelerators but never connects its circuit-level analysis
(Tables II–IV) to workload accuracy.  This benchmark does:

1. **CNN proxy** — a small im2col conv net (conv3x3 -> relu -> pool ->
   linear readout) on synthetic 10-class images, every GEMM routed through
   the engine's ``ref`` backend (``matmul_float``) under each
   organization's ``ChannelModel`` at each N.
   Reports classification accuracy vs the float model.
2. **CNN workload GEMM fidelity** — for each paper CNN workload
   (GoogleNet/ResNet50/MobileNetV2/ShuffleNetV2), the largest-MAC layer's
   GEMM is run through the channel and reported as SQNR [dB] vs the exact
   int8 GEMM.
3. **LM config** — qwen2-0.5b (smoke config) served with prepacked int8
   weights (``prepack_params``) under each organization's channel; reports
   top-1 logit agreement with the float model.

Expected structure (asserted): SMWA — the "hitless" organization with the
smallest loss/penalty chain and no inter-modulation / cross-weight
crosstalk — degrades no faster than ASMW/MASW at matched N.
"""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cnn_workloads import WORKLOADS
from repro.core.dpu import DPUConfig
from repro.kernels.photonic_gemm.ref import exact_int_gemm
from repro.noise import build_channel_model
from repro.orgs import ORGANIZATIONS
from repro.photonic import engine_for

from benchmarks.run import register_benchmark

N_SWEEP = (8, 16, 32, 64)
N_SWEEP_SMOKE = (16,)


# ---------------------------------------------------------------------------
# 1. CNN proxy: im2col conv net on synthetic images
# ---------------------------------------------------------------------------
def _make_images(key, n, classes=10, hw=8):
    """Class-templated 8x8 images + pixel noise."""
    kt, kl, kn = jax.random.split(key, 3)
    templates = jax.random.normal(kt, (classes, hw, hw)) * 2.0
    labels = jax.random.randint(kl, (n,), 0, classes)
    imgs = templates[labels] + jax.random.normal(kn, (n, hw, hw))
    return imgs, labels


def _im2col(x, kh=3, kw=3):
    """(B, H, W) -> (B, H-2, W-2, kh*kw) valid patches."""
    b, h, w = x.shape
    patches = [
        x[:, i : i + h - kh + 1, j : j + w - kw + 1]
        for i in range(kh)
        for j in range(kw)
    ]
    return jnp.stack(patches, axis=-1)


def _cnn_forward(params, imgs, matmul):
    b = imgs.shape[0]
    patches = _im2col(imgs)                      # (B, 6, 6, 9)
    h = matmul(patches.reshape(-1, 9), params["conv"])  # (B*36, 8)
    h = jax.nn.relu(h.reshape(b, 6, 6, -1))
    h = h.reshape(b, 3, 2, 3, 2, -1).mean(axis=(2, 4))  # 2x2 avg pool -> 3x3
    feats = h.reshape(b, -1)                     # (B, 72)
    return matmul(feats, params["readout"])


def _train_cnn(key, imgs, labels, classes=10):
    kc = jax.random.fold_in(key, 1)
    conv = jax.random.normal(kc, (9, 8)) / 3.0
    params = {"conv": conv, "readout": jnp.zeros((72, classes))}
    # Closed-form readout on float features (lstsq ridge).
    b = imgs.shape[0]
    patches = _im2col(imgs)
    h = jax.nn.relu((patches.reshape(-1, 9) @ conv).reshape(b, 6, 6, -1))
    feats = h.reshape(b, 3, 2, 3, 2, -1).mean(axis=(2, 4)).reshape(b, -1)
    onehot = jax.nn.one_hot(labels, classes)
    readout, *_ = jnp.linalg.lstsq(feats, onehot, rcond=None)
    params["readout"] = readout
    return params


def cnn_proxy_accuracy(n_sweep, samples=512):
    key = jax.random.PRNGKey(0)
    imgs, labels = _make_images(key, samples)
    params = _train_cnn(key, imgs, labels)

    float_pred = jnp.argmax(_cnn_forward(params, imgs, jnp.matmul), -1)
    acc_float = float((float_pred == labels).mean())

    table = {}
    for org in ORGANIZATIONS:
        for n in n_sweep:
            ch = build_channel_model(org, n=n, bits=4, datarate_gs=5.0)
            cfg = DPUConfig(
                organization=org, bits=4, dpe_size=n, channel=ch, noise_seed=7
            )
            mm = engine_for(cfg, "ref").matmul_float
            pred = jnp.argmax(_cnn_forward(params, imgs, mm), -1)
            table[(org, n)] = float((pred == labels).mean())
    return acc_float, table


# ---------------------------------------------------------------------------
# 2. Workload GEMM fidelity (largest-MAC layer per paper CNN)
# ---------------------------------------------------------------------------
def _sqnr_db(exact, noisy):
    err = noisy.astype(np.float64) - exact.astype(np.float64)
    p_sig = (exact.astype(np.float64) ** 2).mean()
    p_err = max((err**2).mean(), 1e-30)
    return 10.0 * np.log10(p_sig / p_err)


def workload_gemm_sqnr(n_sweep, max_rows=32, max_cols=64, max_k=512):
    rng = np.random.default_rng(0)
    out = {}
    for wname, fn in WORKLOADS.items():
        layer = max(fn(), key=lambda lay: lay.macs)
        r = min(layer.rows, max_rows)
        k = min(layer.k, max_k)
        c = min(layer.cols, max_cols)
        xq = jnp.asarray(rng.integers(-127, 128, (r, k), dtype=np.int8))
        wq = jnp.asarray(rng.integers(-127, 128, (k, c), dtype=np.int8))
        gold = np.asarray(exact_int_gemm(xq, wq))
        for org in ORGANIZATIONS:
            for n in n_sweep:
                ch = build_channel_model(org, n=n, bits=4, datarate_gs=5.0)
                cfg = DPUConfig(
                    organization=org, bits=4, dpe_size=n, channel=ch,
                    noise_seed=3,
                )
                noisy = np.asarray(engine_for(cfg, "ref").int_gemm(xq, wq))
                out[(wname, layer.name, org, n)] = _sqnr_db(gold, noisy)
    return out


# ---------------------------------------------------------------------------
# 3. LM config: photonic int8 serving under each organization's channel
# ---------------------------------------------------------------------------
def _lm_setup(tokens=16, batch=2):
    """Shared LM fixture: qwen2-0.5b smoke config, float reference logits."""
    from repro.models import registry
    from repro.models.common import init_tree

    arch = registry.get("qwen2-0.5b")
    cfg = dataclasses.replace(arch.smoke_config, remat=False)
    params = init_tree(arch.param_defs(cfg), jax.random.PRNGKey(0), cfg.param_dtype)
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, tokens)), jnp.int32)
    ref_logits, _ = arch.prefill(params, {"tokens": toks}, cfg, tokens)
    return arch, cfg, params, toks, tokens, ref_logits


def _lm_fidelity(setup, channel, seed, n, slicing=None):
    """(rel logit err, top-1 agreement) of photonic int8 serving vs float."""
    from repro.models.common import engine_from_model_config
    from repro.photonic import prepack_params

    arch, cfg, params, toks, tokens, ref_logits = setup
    dpu = DPUConfig(
        organization=channel.organization if channel else "SMWA",
        bits=4,
        dpe_size=n,
        channel=channel,
        noise_seed=seed,
    )
    cfg_q = dataclasses.replace(
        cfg,
        photonic=dpu,
        photonic_backend="ref",
        photonic_slicing=slicing,
    )
    params_q = prepack_params(
        params, arch.param_defs(cfg_q), engine_from_model_config(cfg_q)
    )
    logits, _ = arch.prefill(params_q, {"tokens": toks}, cfg_q, tokens)
    rel = float(jnp.linalg.norm(logits - ref_logits) / jnp.linalg.norm(ref_logits))
    top1 = float(
        (jnp.argmax(logits, -1) == jnp.argmax(ref_logits, -1)).mean()
    )
    return rel, top1


def lm_logit_fidelity(n, tokens=16, batch=2, seeds=(5, 6, 7)):
    """Relative logit error + top-1 agreement of photonic int8 serving vs
    the float model (qwen2-0.5b smoke config, random init — logit error is
    the meaningful metric there; top-1 on near-uniform random-init logits
    flips under any perturbation).  rel_logit_err averages over ``seeds``.

    Finding: at the budgeted per-symbol SNR the LM path is noise-dominated
    for EVERY organization (rel err saturates near/above 1 — global int8
    scaling leaves LM activations far below the modulator full scale, so
    fullscale-referred analog noise swamps them).  The organization
    ordering is carried by the CNN-proxy / SQNR axes; here we check the
    saturation bound and that noise, not quantization, is responsible."""
    setup = _lm_setup(tokens=tokens, batch=batch)
    out = {"ideal": _lm_fidelity(setup, None, seeds[0], n)}
    for org in ORGANIZATIONS:
        ch = build_channel_model(org, n=n, bits=4, datarate_gs=5.0)
        rels, top1s = zip(*(_lm_fidelity(setup, ch, s, n) for s in seeds))
        out[org] = (float(np.mean(rels)), float(np.mean(top1s)))
    return out


PLATFORM_SWEEP = ("SOI", "SIN")
SLICING_SWEEP = (None, 2)


def lm_platform_slicing_grid(
    n,
    tokens=16,
    batch=2,
    seeds=(5, 6, 7),
    platforms=PLATFORM_SWEEP,
    slicings=SLICING_SWEEP,
):
    """Platform x slicing x org LM logit fidelity grid (PR-9 tentpole).

    The escape hatches from the ENOB-saturated baseline measured by
    :func:`lm_logit_fidelity`:

    * **platform** — SiN's ~10x lower propagation loss raises the
      received per-channel power, shrinking the fullscale-referred
      detector sigma (and roughly doubling the achievable N, though this
      grid holds N fixed to isolate the noise effect);
    * **slicing** — 2-bit plane passes shrink the product full-scale by
      ``(2^2-1)^2 / (2^4-1)^2 = 0.04``, and the per-plane noise draws
      recombine with exact digital shifts.

    Keys are ``"{platform}|{plane_bits or 'none'}|{org}"``; values are
    seed-averaged relative logit errors (lower = higher fidelity).
    """
    setup = _lm_setup(tokens=tokens, batch=batch)
    grid = {}
    for platform in platforms:
        for slicing in slicings:
            for org in ORGANIZATIONS:
                ch = build_channel_model(
                    org, n=n, bits=4, datarate_gs=5.0, platform=platform
                )
                rels = [
                    _lm_fidelity(setup, ch, s, n, slicing=slicing)[0]
                    for s in seeds
                ]
                plane = "none" if slicing is None else str(slicing)
                grid[f"{platform}|{plane}|{org}"] = float(np.mean(rels))
    return grid


# ---------------------------------------------------------------------------
def run(smoke=False):
    n_sweep = N_SWEEP_SMOKE if smoke else N_SWEEP
    samples = 128 if smoke else 512
    t0 = time.time()

    acc_float, cnn = cnn_proxy_accuracy(n_sweep, samples=samples)
    print("org_accuracy,cnn_proxy_accuracy_vs_N")
    print("org,n,accuracy,delta_vs_float")
    print(f"float,-,{acc_float:.4f},0.0000")
    for (org, n), acc in sorted(cnn.items()):
        print(f"{org},{n},{acc:.4f},{acc - acc_float:+.4f}")

    sqnr = workload_gemm_sqnr(n_sweep)
    print("org_accuracy,workload_gemm_sqnr_db")
    print("workload,layer,org,n,sqnr_db")
    for (wname, lname, org, n), v in sorted(sqnr.items()):
        print(f"{wname},{lname},{org},{n},{v:.1f}")

    lm_n = min(n_sweep)
    lm = lm_logit_fidelity(lm_n)
    print("org_accuracy,lm_qwen2_0.5b_logit_fidelity")
    print("org,n,rel_logit_err,top1_agreement")
    for org, (rel, top1) in sorted(lm.items()):
        print(f"{org},{lm_n},{rel:.4f},{top1:.4f}")

    grid_kwargs = dict(tokens=8, seeds=(5,)) if smoke else {}
    grid = lm_platform_slicing_grid(lm_n, **grid_kwargs)
    print("org_accuracy,lm_platform_slicing_rel_logit_err")
    print("platform,slicing,org,n,rel_logit_err")
    for key, rel in sorted(grid.items()):
        platform, plane, org = key.split("|")
        print(f"{platform},{plane},{org},{lm_n},{rel:.4f}")

    print(f"# total_s={time.time() - t0:.1f}")
    return {
        "float_accuracy": acc_float,
        "cnn_proxy": {f"{o}_n{n}": v for (o, n), v in cnn.items()},
        "workload_sqnr_db": {
            f"{w}_{o}_n{n}": round(v, 2) for (w, _l, o, n), v in sqnr.items()
        },
        "lm_n": lm_n,
        "lm_rel_logit_err": {o: rel for o, (rel, _) in lm.items()},
        "lm_top1": {o: t for o, (_, t) in lm.items()},
        "lm_platform_slicing": grid,
    }


@register_benchmark("org_accuracy")
def main(smoke=False):
    derived = run(smoke=smoke)
    # Acceptance: SMWA (hitless) degrades no faster than ASMW/MASW at
    # matched N, on every axis we measure.
    cnn = derived["cnn_proxy"]
    n_sweep = N_SWEEP_SMOKE if smoke else N_SWEEP
    for n in n_sweep:
        tol = 0.02
        assert cnn[f"SMWA_n{n}"] >= cnn[f"ASMW_n{n}"] - tol, (n, cnn)
        assert cnn[f"SMWA_n{n}"] >= cnn[f"MASW_n{n}"] - tol, (n, cnn)
    # LM serving is noise-saturated for every organization (see
    # lm_logit_fidelity docstring): check that quantization alone is benign,
    # that the degradation is noise-driven, and a generous saturation bound
    # on SMWA (guards regression to "hitless catastrophically worse").
    lm = derived["lm_rel_logit_err"]
    assert lm["ideal"] < 0.1, lm
    for org in ("ASMW", "MASW", "SMWA"):
        assert lm[org] > lm["ideal"], lm
    assert lm["SMWA"] <= min(lm["ASMW"], lm["MASW"]) + 0.2, lm
    # PR-9 tentpole: the SiN + bit-sliced arm must beat the ENOB-saturated
    # SOI unsliced baseline for every organization — lower-loss platform
    # and plane-referred noise are real fidelity levers, not no-ops.
    grid = derived["lm_platform_slicing"]
    for org in ("ASMW", "MASW", "SMWA"):
        assert grid[f"SIN|2|{org}"] < grid[f"SOI|none|{org}"], (org, grid)
    return derived


if __name__ == "__main__":
    main()
