"""Device ms a step of the ``allreduce`` phase less the exchange's own ops
(``scope_reduce.EXCHANGE``: ``comm.exchange_ms``): the local work the
exchange costs.  Since the exchange is a ring (PR 45) that is the ring's
piece cuts, its adds and the write-backs of the gathered pieces; under
the synchronous ``all-reduce`` it was bucket pack, unpack and wire
casts."""

from chipbench import scope_reduce


def read(ctx):
    return scope_reduce.phase_ms(ctx, "allreduce", without_exchange=True)
