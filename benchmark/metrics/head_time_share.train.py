"""Share of the traced window that is device self time of operations
under the ``head`` (final norm, ``lm_head``) and ``loss`` scopes."""

from benchmark.reduce import program


def read(run):
    return program.scope_share(run, ("head", "loss"))
