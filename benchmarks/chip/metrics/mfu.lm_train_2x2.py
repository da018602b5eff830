"""mfu.lm_train_2x2: the whole step's model FLOPs per second over the bf16
peak of all the chips the step runs on, in percent.

    100 · model_flops / window / (chips · peak)

``model_flops`` is what the cell's ``Driver`` counted for the work it
completed in the traced window, from the published shapes (``flops.py``),
and ``chips`` the size of its mesh; over 100% means the formula or the clock
is wrong. ``mfu.py`` divides by one chip's peak and would read four times
too high here.
"""


def read(ctx):
    f = getattr(ctx["driver"], "facts", None)
    if not f or not f.get("model_flops") or ctx.get("peaks") is None:
        return None
    return (100.0 * f["model_flops"] / f["window_s"]
            / (f["chips"] * ctx["peaks"].flops))
