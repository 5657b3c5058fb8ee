"""Paper §V-B claim check: "prior optical GEMM accelerators show minimal or
no loss in inference accuracy".

We test the claim numerically: a small MLP classifier (synthetic gaussian
clusters) evaluated with (a) exact float GEMMs, (b) the ideal photonic DPU
datapath (int8, bit-sliced, psum-chunked), and (c) the photonic datapath
with analog noise at the level the scalability analysis budgets for
(sigma = sqrt(N)/2 psum LSBs) and beyond.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dpu import DPUConfig, noise_sigma_from_snr
from repro.photonic import engine_for

from benchmarks.run import register_benchmark


def make_data(key, n=2048, d=64, classes=10):
    kc, kx = jax.random.split(key)
    centers = jax.random.normal(kc, (classes, d)) * 2.0
    labels = jax.random.randint(kx, (n,), 0, classes)
    x = centers[labels] + jax.random.normal(jax.random.fold_in(kx, 1), (n, d))
    return x, labels


def make_mlp(key, d=64, h=128, classes=10):
    k1, k2 = jax.random.split(key)
    return {
        "w1": jax.random.normal(k1, (d, h)) / np.sqrt(d),
        "w2": jax.random.normal(k2, (h, classes)) / np.sqrt(h),
    }


def forward(params, x, matmul):
    h = jax.nn.relu(matmul(x, params["w1"]))
    return matmul(h, params["w2"])


def run(smoke=False):
    key = jax.random.PRNGKey(0)
    x, y = make_data(key, n=512 if smoke else 2048)
    params = make_mlp(jax.random.fold_in(key, 7))

    # "train" the readout cheaply: least squares on the hidden features
    h = jax.nn.relu(x @ params["w1"])
    w2, *_ = jnp.linalg.lstsq(h, jax.nn.one_hot(y, 10), rcond=None)
    params["w2"] = w2

    exact_pred = jnp.argmax(forward(params, x, jnp.matmul), -1)
    acc_exact = float((exact_pred == y).mean())

    print("noise_accuracy,exact_vs_photonic")
    print("config,accuracy,agreement_with_exact")
    print(f"float_exact,{acc_exact:.4f},1.0000")
    t0 = time.time()
    derived = {"float_exact": acc_exact}
    orgs = (("SMWA", 5),) if smoke else (("SMWA", 5), ("ASMW", 5))
    mults = (0.0, 4.0) if smoke else (0.0, 1.0, 4.0, 16.0)
    for org, dr in orgs:
        for noise_mult in mults:
            cfg = DPUConfig(organization=org, bits=4, datarate_gs=dr)
            sigma = noise_mult * noise_sigma_from_snr(cfg)
            cfg = DPUConfig(
                organization=org, bits=4, datarate_gs=dr, noise_sigma_lsb=sigma
            )
            eng = engine_for(cfg, "ref")
            mm = lambda a, b: eng.matmul_float(  # noqa: E731
                a, b, prng_key=jax.random.PRNGKey(3)
            )
            pred = jnp.argmax(forward(params, x, mm), -1)
            acc = float((pred == y).mean())
            agree = float((pred == exact_pred).mean())
            derived[f"{org}_dr{dr}_noise{noise_mult:g}x"] = acc
            print(f"{org}_dr{dr}_noise{noise_mult:g}x,{acc:.4f},{agree:.4f}")
    n_evals = len(orgs) * len(mults)
    print(f"# us_per_eval={(time.time()-t0)*1e6/n_evals:.0f}")
    return derived


@register_benchmark("noise_accuracy")
def main(smoke=False):
    return run(smoke=smoke)


if __name__ == "__main__":
    main()
