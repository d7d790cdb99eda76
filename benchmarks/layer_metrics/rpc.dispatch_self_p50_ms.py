"""Self time of the op's `rpc.<op>` span and its `rpc.reply` (pickling and
sending the answer): what the dispatch thread spends outside the frontend
call. Median over the traced window's measured ops."""
import _spans


def read(ctx):
    return _spans.p50_ms(_spans.part(ctx, "rpc"))
