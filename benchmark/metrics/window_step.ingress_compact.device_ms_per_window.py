"""Device ms per window of the chain ops in window_step's
`ingress_compact` scope: the surviving-ingress compaction."""
from benchmark import scopes

read = scopes.reader(__file__, "ingress_compact")
