"""Model FLOPs of the steps run in the traced window (forward and backward,
no recomputation) over the window times the chips' bf16 peak."""


def read(run):
    red, tr = run.get("trace"), run.get("train")
    if red is None or not tr or not tr["traced_steps"]:
        return None
    flops = tr["traced_steps"] * tr["tokens_per_step"] * tr["flops_per_token"]
    return 100.0 * flops / (red.window_s * run["chips"] * run["peaks"]["flops_bf16"])
