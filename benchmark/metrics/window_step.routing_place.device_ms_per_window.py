"""Device ms per window of the chain ops in window_step's
`routing_place` scope: landing routed packets in the ingress rows."""
from benchmark import scopes

read = scopes.reader(__file__, "routing_place")
