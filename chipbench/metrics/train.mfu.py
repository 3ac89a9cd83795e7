"""Model FLOP/s utilization of the training steps: the model FLOPs of the
steps in the program's ``train_step`` spans (``repro.launch.train.step_loop``)
over their seconds and the chips' bf16 peak.  FLOPs a token:
``lib/flops.py`` (6 N plus causal attention; recomputation not counted)."""
from lib.flops import train_flops_per_token
from lib.spans import walk


def read(ctx):
    steps = [s for s, _ in walk(ctx.spans) if s["name"] == "train_step"]
    secs = sum(s["seconds"] for s in steps)
    tokens = sum(int(s.get("attrs", {}).get("tokens", 0)) for s in steps)
    if secs <= 0 or tokens <= 0:
        return None
    flops = tokens * train_flops_per_token(ctx.config["model"], ctx.traffic["seq"])
    return 100.0 * flops / secs / (ctx.chips * ctx.peaks()["bf16_flops_per_s"])
