"""Prompt tokens prefilled in the window / window seconds (host clock):
the valid rows of every prefill step that started in the window, as the
benchmark's loop records them."""


def read(ctx):
    n = sum(ln - st for _, _, _, lens, starts in ctx.steps_in("prefill")
            for ln, st in zip(lens, starts))
    return n / ctx.window_s if n else None
