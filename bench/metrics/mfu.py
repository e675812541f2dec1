"""The FLOPs the window's work requires (``bench/flops``: LeNet forward
and backward for every local SGD step, plus the evaluation forwards; the
MAML re-cluster left out, so a lower bound) over the traced window and
the chips' bf16 peak (``bench/peaks.json``), in percent."""


def read(ctx):
    red = ctx["trace"]
    if not ctx["peak_flops"] or red["window_s"] <= 0:
        return None
    done = ctx["flops_per_unit"] * ctx["units"]
    return 100.0 * done / red["window_s"] / (ctx["chips"]
                                             * ctx["peak_flops"])
