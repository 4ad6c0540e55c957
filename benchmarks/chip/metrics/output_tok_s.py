"""Output tokens the engine returned for the window's requests, over the
window's seconds (host clock).  Every request the window took completes
inside it."""
import derive


def read(run):
    return derive.output_tokens(run) / run.window_s
