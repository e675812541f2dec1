"""Device milliseconds per round (sync) or event (async) of LeNet's
convolutions and their bias adds (``models/lenet.py`` ``_conv``): every
operation with a path component ``lenet_conv``, bare (eval) or wrapped by
a transform (``jvp(lenet_conv)`` forward, ``transpose(jvp(lenet_conv))``
backward, under ``vmap(...)``).  A program without that scope reads
nothing."""
import re

SCOPE = re.compile(r"(^|[/(])lenet_conv([/):]|$)")


def read(ctx):
    s = sum(v for k, v in ctx["trace"]["scope_s"].items() if SCOPE.search(k))
    return s / ctx["units"] * 1e3 if s > 0 else None
