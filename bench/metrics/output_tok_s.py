"""Generated tokens emitted in the window over the window's seconds: all
the work of every step that started in it, over all of its time."""


def read(rec):
    return sum(t.tokens for t in rec.ticks) / rec.window_s
