"""Device ms per window of the chain ops in the chain's `respawn` and
`ingest_rows` scopes: PHOLD's respawn batch and its append."""
from benchmark import scopes

read = scopes.reader(__file__, "respawn", "ingest_rows")
