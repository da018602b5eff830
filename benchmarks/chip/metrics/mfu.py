"""mfu.<cell kind>: the whole step's model FLOPs per second over the chip's
bf16 peak, in percent.

    100 · model_flops / window / peak

``model_flops`` is what the driver counted for the work it completed in
the traced window, from the published shapes (``flops.py``): the matrix
products a step has to do however it does them, so a share over 100%
means the formula or the clock is wrong. One reader for every ``mfu.*``
metric; the suffix only names the end-to-end metric it moves.
"""


def read(ctx):
    f = getattr(ctx["driver"], "facts", None)
    if not f or not f.get("model_flops") or ctx.get("peaks") is None:
        return None
    return 100.0 * f["model_flops"] / f["window_s"] / ctx["peaks"].flops
