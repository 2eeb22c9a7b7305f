"""Host milliseconds per window step in the ``batch_at`` handed to
run_training: making the step's rows and placing them on the mesh."""


def read(ctx):
    w = ctx["window"]
    return 1e3 * sum(w["input_s"]) / w["steps"]
