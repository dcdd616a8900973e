"""Device ms per window of the chain ops in window_step's `routing_rank`
scope: the routing order and bucket offsets."""
from benchmark import scopes

read = scopes.reader(__file__, "routing_rank")
