"""Paper Fig 6: full-precision CNN inference — PIM upper bound vs GPU/TPU.

Methodology as the paper's §5: the PIM number counts only the matmul/conv
MACs (an upper bound); the accelerator numbers come from the compiled step's
cost analysis (flops, bytes — our stand-in for the Nsight counters).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.analyzer import Workload, analyze, pim_time
from repro.core.costmodel import A6000, MEMRISTIVE_PIM, TPU_V5E
from repro.models import cnn

from .common import time_fn

BATCH = 8


def _measure(name: str, train: bool = False):
    init, apply = cnn.MODELS[name]
    params = init(jax.random.PRNGKey(0))
    x = jnp.zeros((BATCH, 224, 224, 3), jnp.float32)

    if train:
        def step(p, x):
            def loss(p):
                out = apply(p, x, train=True)
                return (out.astype(jnp.float32) ** 2).mean()
            return jax.grad(loss)(p)
        fn = jax.jit(step)
    else:
        fn = jax.jit(lambda p, x: apply(p, x))
    lowered = fn.lower(params, x)
    compiled = lowered.compile()
    ca = compiled.cost_analysis()
    # fusion-aware bytes: raw CPU 'bytes accessed' understates reuse ~5-30×
    # (unfused elementwise), which would flip the paper's Fig-7 verdict
    from repro.core.roofline import analyze_hlo

    a = analyze_hlo(compiled.as_text(), default_group=1)
    bytes_ = a.hbm_bytes or float(ca.get("bytes accessed", 0.0))
    us = time_fn(fn, params, x, warmup=1, iters=2)
    return float(ca.get("flops", 0.0)), bytes_, us


def run(train: bool = False) -> list[dict]:
    rows = []
    for name in ("alexnet", "googlenet", "resnet50"):
        flops, bytes_, us = _measure(name, train=train)
        w = Workload(name, flops=flops, hbm_bytes=bytes_)
        t_pim = pim_time(w)  # matmul/conv MACs only — paper's upper bound
        t_gpu_comp = flops / A6000.peak_fp32
        t_gpu_mem = bytes_ / A6000.mem_bw
        t_gpu = max(t_gpu_comp, t_gpu_mem)
        t_tpu = max(flops / TPU_V5E.peak_bf16, bytes_ / TPU_V5E.hbm_bw)
        tag = "fig7" if train else "fig6"
        rows.append({
            "name": f"{tag}/{name}",
            "us_per_call": f"{us:.0f}",
            "flops_per_batch": f"{flops:.3g}",
            "reuse_flops_per_byte": f"{flops/bytes_:.1f}",
            "pim_imgs_per_s": f"{BATCH/t_pim:.1f}",
            "gpu_exp_imgs_per_s": f"{BATCH/t_gpu:.1f}",
            "gpu_theo_imgs_per_s": f"{BATCH/t_gpu_comp:.1f}",
            "tpu_imgs_per_s": f"{BATCH/t_tpu:.1f}",
            "pim_beats_gpu": str(t_pim < t_gpu),
            "pim_eff_imgs_per_j": f"{BATCH/t_pim/MEMRISTIVE_PIM.max_power_w:.2f}",
            "gpu_eff_imgs_per_j": f"{BATCH/t_gpu/A6000.max_power_w:.2f}",
        })
    return rows


def main():
    from repro import compile_cache

    from .common import emit

    compile_cache.enable()
    emit(run(train=False))


if __name__ == "__main__":
    main()
