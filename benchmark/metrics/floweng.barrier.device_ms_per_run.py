"""Device ms per run of the flow engine's ops in its `flow_barrier` scope:
the end-of-window ACK flush and clock update."""
from benchmark import flowscopes

read = flowscopes.reader(__file__, "flow_barrier")
