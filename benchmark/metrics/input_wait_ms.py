"""Device idle time per step while the host fetches the next batch or
moves it to the device (milliseconds): idle time under the program's
``train.next_batch`` and ``train.to_device`` spans, averaged over the
devices."""

from benchmark import program_spans


def read(ctx):
    return program_spans.idle_ms(ctx, ("train.next_batch", "train.to_device"))
