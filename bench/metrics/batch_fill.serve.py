"""Mean dispatched batch over the server's largest batch, in %, from
`SpikeServer.stats()` over the window."""


def read(run):
    if not run.server_stats.get("batches"):
        return None
    return 100.0 * run.server_stats["mean_batch_size"] / \
        run.plan.max_batch
