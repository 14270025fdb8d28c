"""compile_s: seconds of XLA compilation during set-up (the compile events
``repro.analysis.recompile.CompileWatcher`` counts; a persistent-cache hit
counts its read time). Moves setup_s."""


def read(ctx):
    return ctx.compile_s
