"""Pallas TPU kernels for the perf-critical hot spots.

  gram          — matrix-free multi-kernel Gram subsystem: tiled (signed)
                  Gram + batched matvec for every KernelSpec family
                  (rbf / laplacian / poly / linear), one shared
                  accumulation skeleton (SODM nonlinear-kernel hot spot)
  dual_cd_block — VMEM-tile Gauss-Southwell dual CD (TPU adaptation of
                  Eqn. 3) + the fused pass kernel (tile sweeps and the
                  cross-tile Gram matvec in one pallas_call per pass)
  odm_grad      — fused single-pass linear primal ODM gradient (DSVRG)
  flash_attn    — causal/sliding-window GQA flash attention (LM substrate)

Use :mod:`repro.kernels.ops` from framework code (padding + fallbacks);
:mod:`repro.kernels.ref` holds the pure-jnp oracles.
"""
from repro.kernels import ops, ref

__all__ = ["ops", "ref"]
