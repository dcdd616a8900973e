"""Device ms per run of the flow engine's ops in its `flow_sched` scope:
the scheduled events (ring arrivals, timers, opens) of each fused step."""
from benchmark import flowscopes

read = flowscopes.reader(__file__, "flow_sched")
