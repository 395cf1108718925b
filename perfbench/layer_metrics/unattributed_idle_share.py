"""Share of the device's idle seconds, over the gaps the reduction lists,
for which nothing says what the host was doing: the gaps that
``lib/trace._name_gap`` names "host: no span (Python)", and those it names
by a span that only holds the job's phases (``rdp.train.job``,
``rdp.train.epoch``). A parent's name on a gap means that the phase the
host was in has no span: once the job spans the window there is no gap
without a span, and a phase that a later change adds without a stage shows
here as its parent's seconds."""

NO_SPAN = "host: no span (Python)"
PARENTS = ("rdp.train.job", "rdp.train.epoch")


def unattributed(name: str) -> bool:
    # a gap is named "<thread>: <span>"
    return name == NO_SPAN or name.split(": ", 1)[-1] in PARENTS


def read(ctx):
    gaps = ctx.trace.gaps if ctx.trace is not None else []
    idle = sum(seconds for seconds, _ in gaps)
    if idle <= 0:
        return None
    return 100.0 * sum(s for s, name in gaps if unattributed(name)) / idle
