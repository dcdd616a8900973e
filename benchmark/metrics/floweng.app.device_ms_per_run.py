"""Device ms per run of the flow engine's ops in its `flow_app` scope:
the inline read, write and close updates of each fused step."""
from benchmark import flowscopes

read = flowscopes.reader(__file__, "flow_app")
