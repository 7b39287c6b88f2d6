"""tokens_per_s: decode tokens committed in the window over the window's
seconds. The window ends when the step that crosses ``--seconds`` has
returned its attention over every layer, so every token counted is complete
and every second of the window is counted."""


def read(run):
    w = run.window
    return w.tokens / w.elapsed_s if w.elapsed_s > 0 else None
