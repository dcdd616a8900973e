"""Device ms per window of the chain ops in window_step's
`egress_compact` scope: the leftover-egress compaction."""
from benchmark import scopes

read = scopes.reader(__file__, "egress_compact")
