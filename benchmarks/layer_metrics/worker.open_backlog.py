"""Churn workflows the window started that no worker had completed when
the window's last reply came: a backlog that grows with the window means
the workers or the decision path are the knee."""


def read(ctx):
    if ctx.get("kind") != "serve":
        return None
    return float(ctx["open_backlog"])
