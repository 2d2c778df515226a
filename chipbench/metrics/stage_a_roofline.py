"""Stage A (neighbour search, ``knn_topk*``): percent of its roofline
over the window's reads (trace against ``peaks.json``)."""
import roofline


def read(ctx):
    return roofline.share(ctx, r"knn_topk", lambda q: roofline.stage_a(
        q, ctx.cfg["n_users"], ctx.cfg["n_items"]))
