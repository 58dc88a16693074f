"""The program's own fifo queue machine (``ra_tpu.models.fifo``, after
upstream's ``test/ra_fifo.erl``): enqueue, checkout with credit, settle,
return, consumer down by monitor, release cursor once all is settled."""


def make(args):
    from ra_tpu.models.fifo import FifoMachine

    return FifoMachine()
