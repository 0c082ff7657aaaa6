"""The fullest held expert's load over the mean held expert's, over the window:
the round's sum over steps and expert layers of the largest group
(``moe.expert_load_max``) over the sum of the mean group
(``moe.expert_load_mean``).  1 is even routing; the grouped products' work is
the mean's, their longest group the max's.  Silent where the program keeps no
such counters."""


def read(ctx):
    log = getattr(getattr(ctx.driver, "sim", None), "round_log", None)
    if not log or not ctx.units:
        return None
    rounds = log[-len(ctx.units):]
    if any("moe.expert_load_max" not in r or "moe.expert_load_mean" not in r for r in rounds):
        return None
    mean = sum(r["moe.expert_load_mean"] for r in rounds)
    return sum(r["moe.expert_load_max"] for r in rounds) / mean if mean else None
