"""Decode steps' share of the chip's bf16 peak: the operations the active
lanes' tokens need over the decode program's device time in the traced
window."""


def read(ctx):
    s, calls = ctx.get("trace"), ctx.get("calls") or {}
    prog = "jit__decode_fn"
    if not s or prog not in calls or not s["module_s"].get(prog):
        return None
    flops = calls[prog]["flops_mean"] * s["module_n"].get(prog, 0)
    return 100.0 * flops / s["module_s"][prog] / ctx["peaks"][
        "bf16_flops_per_s"]
