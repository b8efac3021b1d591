"""Share of the views' key rows that the decode ticks' attention scored: rows
scored / (slots x view rows x layers), summed over the window's ticks
(ServingStats ``decode_attn_rows_share``: host arithmetic from each lane's
position and the program's own key-block rule; its twin
``decode_attn_rows_fill`` is the rows the running streams can see over the
rows scored). 100 means every tick scored every lane's whole ``max_len``-long
view whatever its query could see. None where the program has no such
counter."""


def compute(ctx):
    value = ctx.stats.get("decode_attn_rows_share")
    return None if value is None else 100.0 * value
