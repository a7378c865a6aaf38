"""Share of its roofline the flash forward kernel reaches in the traced
window's latent-attention prefills, in percent.  Each kernel event is one
layer's attention over the prompt of the ``bench.prefill.<length>`` span
around it; its least time comes from the work the arch module counts at
that length (``flash_fwd``: q k^T at the query-key width, p v at the value
width, causal pairs only), so the zeros that pad v to the query-key width
and the masked blocks above the diagonal count as share lost."""
from bench import flops, harness

# the forward kernel's Pallas call returns (bf16 output, f32 lse)
KERNEL = (r"= \(bf16\[\d+,\d+,\d+,\d+\][^=]*\) custom-call\("
          r".*tpu_custom_call")


def read(run):
    if run.trace is None:
        return None
    arch = harness.arch_module(run.config)
    if not hasattr(arch, "flash_fwd"):
        return None
    need = took = 0.0
    for op in run.trace.kernel_ops(KERNEL):
        span = run.trace.host_at((op.start + op.end) // 2)
        if not span.startswith("bench.prefill."):
            continue
        S = int(span.rsplit(".", 1)[1])
        f, b = arch.flash_fwd(run.config, S)
        need += flops.roofline_s(f, b, run.peak)
        took += (op.end - op.start) * 1e-9
    return None if took == 0 else 100.0 * need / took
