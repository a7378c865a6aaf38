"""Share of their roofline the flash kernels (forward, and the backward's
dq and dk/dv) reach in the traced training window, in percent.  Each
event is one layer's attention over one chip's rows at the job's length."""
from bench import flops

# the kernels' Pallas calls by what they return: the forward (bf16
# output, f32 lse), dq (one 4-d f32 array) and dk/dv (two 3-d f32 arrays)
PALLAS = r" custom-call\(.*tpu_custom_call"
KERNELS = {r"= \(bf16\[\d+,\d+,\d+,\d+\][^=]*\)" + PALLAS: flops.flash_fwd,
           r"= f32\[\d+,\d+,\d+,\d+\]\{[^=]*\}" + PALLAS: flops.flash_bwd_dq,
           r"= \(f32\[\d+,\d+,\d+\]\{[^=]*\}, f32\[\d+,\d+,\d+\]\{[^=]*\}\)"
           + PALLAS: flops.flash_bwd_dkv}


def read(run):
    if run.trace is None or run.kind != "train":
        return None
    B, S = int(run.traffic["batch_per_chip"]), int(run.traffic["seq_len"])
    need = took = 0.0
    for pattern, work in KERNELS.items():
        f, b = work(run.config, B, S)
        for op in run.trace.kernel_ops(pattern):
            need += flops.roofline_s(f, b, run.peak)
            took += (op.end - op.start) * 1e-9
    return None if took == 0 else 100.0 * need / took
