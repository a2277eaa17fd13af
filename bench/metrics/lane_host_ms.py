"""Mean host ms per dispatch of `Deployment.run_lanes`' lane-state and
schedule stages, from the program's own spans: `run_lanes.encode`,
`.keys`, `.upload` and `.download`, summed over the window by the
dispatcher (`SpikeServer.stats()["stages"]`)."""
from benchkit.program_trace import stage_mean_ms

STAGES = ("run_lanes.encode", "run_lanes.keys", "run_lanes.upload",
          "run_lanes.download")


def read(run):
    return stage_mean_ms(run, STAGES)
