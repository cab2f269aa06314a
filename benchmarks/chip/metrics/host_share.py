"""Share of the window the serve loop spent outside its step calls
(scheduling, admission, page bookkeeping, argmax), in percent."""


def read(ctx):
    inside = sum(min(b, ctx.t1) - a for _, a, b, _, _ in ctx.steps_in())
    return 100.0 * (1.0 - inside / ctx.window_s)
