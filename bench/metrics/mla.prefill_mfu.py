"""Model FLOPs of the window's prefills, counted by the configuration's
arch module at latent attention's own widths and the held expert share
(``prefill_flops``), over their summed call time and the chip's bf16
peak, in percent (host clock)."""
from bench import harness


def read(run):
    if run.kind != "serve":
        return None
    arch = harness.arch_module(run.config)
    if not hasattr(arch, "prefill_flops"):
        return None
    w0, w1 = run.window
    calls = [c for c in run.tx.calls
             if c.kind == "prefill" and w0 <= c.t0 and c.t1 <= w1]
    if not calls:
        return None
    work = sum(arch.prefill_flops(run.config, c.size) for c in calls)
    t = sum(c.t1 - c.t0 for c in calls)
    return 100.0 * work / t / (run.chips * run.peak["bf16_flops"])
