"""Stage B (blend and top-n, ``blend_topn*``): percent of its roofline
over the window's reads (trace against ``peaks.json``)."""
import roofline


def read(ctx):
    return roofline.share(ctx, r"blend_topn", lambda q: roofline.stage_b(
        q, ctx.cfg["k_neighbors"], ctx.cfg["n_items"]))
