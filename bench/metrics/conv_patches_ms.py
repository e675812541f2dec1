"""Device milliseconds per round (sync) or event (async) of the
operations that build LeNet's im2col patches (``models/lenet.py``
``_conv``): every operation with a path component ``conv_patches``,
bare (eval) or wrapped by a transform (``jvp(conv_patches)`` forward,
``transpose(jvp(conv_patches))`` backward, under ``vmap(...)``)."""
import re

SCOPE = re.compile(r"(^|[/(])conv_patches([/):]|$)")


def read(ctx):
    s = sum(v for k, v in ctx["trace"]["scope_s"].items() if SCOPE.search(k))
    return s / ctx["units"] * 1e3 if s > 0 else None
