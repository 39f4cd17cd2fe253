"""Model FLOPs of every token the traced window processed (prefill chunks
at their real widths and decode tokens of live slots) over the window times
the chips' bf16 peak."""


def read(run):
    red, sv = run.get("trace"), run.get("serve")
    if red is None or not sv:
        return None
    flops = sum(f for f, _, traced in sv["work"]["prefill"] + sv["work"]["decode"]
                if traced)
    if flops <= 0:
        return None
    return 100.0 * flops / (red.window_s * run["chips"] * run["peaks"]["flops_bf16"])
