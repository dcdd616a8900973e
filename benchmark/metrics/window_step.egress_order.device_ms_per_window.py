"""Device ms per window of the chain ops in window_step's `egress_order`
scope: qdisc keys, the egress row sort, the token gate."""
from benchmark import scopes

read = scopes.reader(__file__, "egress_order")
