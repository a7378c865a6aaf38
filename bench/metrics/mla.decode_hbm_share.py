"""Bytes one decode call must read from HBM, over the median decode call
in the window and the chip's HBM bandwidth, in percent (host clock).  The
bytes: the weights a token reads (every leaf but the routed experts and
the embedding, and the held experts' expected share of the top k, from
the arch module's ``decode_weight_bytes``), and the latent rows that hold
a token of a decoded slot, from the executor's counters
``repro.mla.latent_rows_live`` over ``repro.decode.calls`` (the mean over
the run's decode calls).  Rows the step gathers beyond those are not
counted: they are work the step need not do."""
from bench import harness
from bench.harness import pctl

LIVE, CALLS = "repro.mla.latent_rows_live", "repro.decode.calls"


def read(run):
    if run.kind != "serve":
        return None
    counters = getattr(run.out.get("executor"), "metrics", None)
    arch = harness.arch_module(run.config)
    if (counters is None or LIVE not in counters or CALLS not in counters
            or not hasattr(arch, "decode_weight_bytes")):
        return None
    calls = counters.counter(CALLS).value
    rows = counters.counter(LIVE).value / calls
    nbytes = (arch.decode_weight_bytes(run.config)
              + rows * arch.latent_row_bytes(run.config))
    w0, w1 = run.window
    d = [c.t1 - c.t0 for c in run.tx.calls
         if c.kind == "decode" and w0 <= c.t0 and c.t1 <= w1]
    if not d:
        return None
    return 100.0 * nbytes / pctl(d, 50) / run.peak["hbm_bytes_per_s"]
