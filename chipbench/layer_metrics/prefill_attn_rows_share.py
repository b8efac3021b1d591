"""Share of the views' key rows that the prefill chunks' attention scored:
rows scored / (rows of the view x layers), summed over the window's chunks
(ServingStats ``prefill_attn_rows_share``: host arithmetic from each chunk's
offset, the layers' windows and the program's own key-block rule). 100 means
every chunk scored its whole ``max_len``-long view whatever its queries could
see; a program that reads only the key blocks up to the chunk's last query
(from the window's start on a windowed layer) reads the traffic's own share.
None where the program has no such counter."""


def compute(ctx):
    value = ctx.stats.get("prefill_attn_rows_share")
    return None if value is None else 100.0 * value
