"""Share of the window's expert picks that landed on zero-compute experts:
picks that return the token itself and read no weights (ServingStats
``moe_zero_pick_share`` = ``moe_zero_picks`` / all picks, over the window's
ticks and prefill chunks). About a third for a router that spreads its picks
evenly over 512 routed and 256 zero-compute columns. None where the program
has no such counter."""


def compute(ctx):
    value = ctx.stats.get("moe_zero_pick_share")
    return None if value is None else 100.0 * value
