"""Of the window's expert assignments (tokens x experts per token x expert
layers x steps), the share that landed on the experts held here:
``moe.assignments_local`` over ``moe.assignments_total``, the compiled round's
own sums (``XLASimulator.round_log``).  8 of 256 experts under even routing
give 3.125 %.  Silent where the program keeps no such counters."""


def read(ctx, numerator="moe.assignments_local", denominator="moe.assignments_total"):
    log = getattr(getattr(ctx.driver, "sim", None), "round_log", None)
    if not log or not ctx.units:
        return None
    rounds = log[-len(ctx.units):]
    if any(numerator not in r or denominator not in r for r in rounds):
        return None
    total = sum(r[denominator] for r in rounds)
    return 100.0 * sum(r[numerator] for r in rounds) / total if total else None
