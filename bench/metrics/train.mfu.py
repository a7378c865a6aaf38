"""Model FLOPs per step (6 x active parameters x tokens plus causal
attention, recomputation not counted) times steps per second in the
window, over chips x bf16 peak, in percent."""
from bench import flops


def read(run):
    if run.kind != "train" or not run.out["steps"]:
        return None
    t0, t1 = run.window
    work = flops.train_step_flops(run.config, run.out["rows"],
                                  int(run.traffic["seq_len"]))
    rate = len(run.out["steps"]) * work / (t1 - t0)
    return 100.0 * rate / (run.chips * run.peak["bf16_flops"])
