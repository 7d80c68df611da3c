"""Mean time of one flush of the IOR ranks' epoch, in ms: take_epoch,
patterns and grammar, encode, the inter-rank reduce and the segment
commit."""


def read(ctx):
    f = ctx["spans"].get("flush")
    return 1e3 * sum(f) / len(f) if f else None
