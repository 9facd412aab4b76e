"""Prompt tokens consumed plus tokens generated by the steps that ended
inside the window, over the window's seconds."""


def read(ctx):
    steps = ctx.window_steps()
    return sum(s.prompt_tokens + s.new_tokens for s in steps) / ctx.seconds
