"""Share of the rows the decode ticks' queries could see that their latent
attention read: ServingStats ``dsa_selected_share`` = ``dsa_selected_rows`` /
``dsa_visible_rows``, summed over the window's ticks, their running lanes and
their layers (host arithmetic from each lane's position: ``min(index_topk,
pos + 1)`` of ``pos + 1``). About 15 % at 2048 of ~14k rows. None where the
program has no learned indexer."""


def compute(ctx):
    value = ctx.stats.get("dsa_selected_share")
    return 100.0 * value if value else None
