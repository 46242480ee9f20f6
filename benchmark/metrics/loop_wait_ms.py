"""Device idle time per step while the host runs the training loop's own
work (milliseconds): idle time under the program's ``train.resolve``
(backend resolution, step lookup), ``train.dispatch`` (enqueueing the
step), ``train.log`` and ``train.checkpoint`` spans, averaged over the
devices."""

from benchmark import program_spans


def read(ctx):
    return program_spans.idle_ms(
        ctx, ("train.resolve", "train.dispatch", "train.log",
              "train.checkpoint"))
