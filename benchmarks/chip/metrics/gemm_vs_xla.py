"""gemm_vs_xla: device time of XLA's own ``jnp.dot`` on the pass's GEMMs
(same shapes, bf16 in, f32 out, traced in the same run) over the device
time of the ``covenant_matmul`` calls (the tiler's kernel with its pads),
per pass.  Above 1 the Covenant-tiled calls are faster."""


def read(r):
    cov = r.call_s.get("gemm", 0.0)
    if cov <= 0 or not r.xla_s or r.xla_reps <= 0:
        return None
    xla = sum(m * s for m, s in zip(r.xla_mult, r.xla_s)) / r.xla_reps
    if xla <= 0:
        return None
    return xla / (cov / r.passes)
