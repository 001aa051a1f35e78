"""Cloud micro-batcher: mean size of the window's cloud micro-batches as a
share of the cell's largest batch (%)."""


def read(run):
    if not run.batch_sizes:
        return None
    return 100.0 * sum(run.batch_sizes) / len(run.batch_sizes) / run.max_batch
