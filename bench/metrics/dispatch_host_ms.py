"""Mean per dispatch of the host time inside `Deployment.run_lanes`
(the harness's "bench.run_lanes" span) in which no device op ran: lane
state and schedule encode, transfers, waiting on the host."""
from benchkit.stats import mean_host_ms


def read(run):
    return mean_host_ms(run)
