"""Share of the summed device-operation time spent in instructions of scopes
``mx.embed``, ``mx.head`` and ``mx.loss`` (embeddings, the MLM/NSP heads or
the tied LM head, the loss and its scaling; forward and backward), in
percent. Layer: model blocks."""
from _scoped import reading


def read(ctx):
    return reading(ctx, "embed_head_loss_busy_share")
