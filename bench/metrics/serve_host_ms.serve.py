"""Mean host ms per dispatch of the dispatcher's own work around
`run_lanes`, from the program's own spans: `serve.assemble` (expiry,
stack, lane lookup, undo snapshot) and `serve.resolve` (results, span
and metric commits, futures), summed over the window by the dispatcher
(`SpikeServer.stats()["stages"]`)."""
from benchkit.program_trace import stage_mean_ms

STAGES = ("serve.assemble", "serve.resolve")


def read(run):
    return stage_mean_ms(run, STAGES)
