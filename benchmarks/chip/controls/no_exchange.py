"""A control of the job path: the batch join with the exchange between
chips left out.

The program runs as the configuration states, except that the shuffle's
``all_to_all`` returns its input: each device keeps the send buffers it
packed for every device, so no tuple bound for a reducer on another chip
reaches it, and those reducers' joins go missing.  It breaks the guarantee
that every tuple reaches each reducer the plan routes it to.  On one chip
there is nothing to exchange and the control is the program itself, so it
serves cells on more than one chip.
"""
import contextlib

from chipbench.job import BatchJoin


class _Over:
    """``base`` with some attributes replaced."""

    def __init__(self, base, **replaced):
        self._base, self._replaced = base, replaced

    def __getattr__(self, name):
        if name in self._replaced:
            return self._replaced[name]
        return getattr(self._base, name)


def _kept(x, axis_name, split_axis, concat_axis, **_):
    return x


@contextlib.contextmanager
def _without_exchange():
    from repro.mapreduce import shuffle

    jax = shuffle.jax
    shuffle.jax = _Over(jax, lax=_Over(jax.lax, all_to_all=_kept))
    try:
        yield
    finally:
        shuffle.jax = jax


class NoExchange(BatchJoin):
    def ingest(self, data):
        with _without_exchange():
            return super().ingest(data)


def make_program(cell, devices, settings, trace):
    return NoExchange(cell, devices, settings, trace)
