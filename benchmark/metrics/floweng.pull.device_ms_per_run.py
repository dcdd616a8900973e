"""Device ms per run of the flow engine's ops in its `flow_pull` scope:
the pull loop and the scatter into the peer rings of each fused step."""
from benchmark import flowscopes

read = flowscopes.reader(__file__, "flow_pull")
