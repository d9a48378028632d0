"""Every output token emitted in the window over the window's seconds
(host clock; a token counts when it reached the host)."""


def read(run):
    return len(run.tokens_in_window()) / run.window_s
