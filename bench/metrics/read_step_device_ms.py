"""Engine steps (``core/engine.py``): device milliseconds per execution of a
read-step program (dense or frontier-sparse), from the profiler trace's XLA
program lines. Moves ``read_p95_ms``."""

from bench import trace_reduce as tr

PROGRAMS = (r"^jit_(_read_body|read_step)",)


def read(ctx):
    if ctx.trace is None:
        return None
    n, secs = tr.program_executions(ctx.trace, ctx.window, PROGRAMS)
    return 1e3 * secs / n if n else None
