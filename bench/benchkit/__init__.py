"""The chip benchmark's harness: everything that measures, and nothing
that is measured.

`bench/run.py` runs one cell of `BENCHMARK.json` once. The harness finds
each cell's parts by name, so a new configuration, traffic mix or metric
is a new file plus an entry in `BENCHMARK.json`:

    bench/configs/<config>.json         sizes of one network, and its
                                        deploy target
    bench/networks/<kind>.py            builds a network of that kind
    bench/traffic/<traffic>.json        parameters of one traffic mix
    bench/traffic/inputs/<kind>.py      makes the spike windows it sends
    bench/metrics/<metric>.py           reads one metric from a run

A configuration's `target` is `engine` or `hiaer`, on one chip, or
`mesh`, its cores spread evenly over the cell's chips; hiaer and mesh
state their hierarchy in the keys `servers`, `fpgas_per_server`,
`cores_per_fpga` and `neurons_per_core` (`benchkit.program`).

Only `benchkit.program` imports the system under test (`repro`). The
plain reference (`benchkit.reference`), the network builders, the
traffic and the trace reduction import nothing of it.
"""
