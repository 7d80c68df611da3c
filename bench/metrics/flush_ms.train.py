"""Mean time of one streaming flush inside the training job, in ms: the
record calls in which the recorder's epoch count moved (take_epoch,
patterns and grammar, encode, reduce, segment commit)."""


def read(ctx):
    f = ctx["spans"].get("flush")
    return 1e3 * sum(f) / len(f) if f else None
