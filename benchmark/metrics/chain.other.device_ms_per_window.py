"""Device ms per window of the chain ops in none of the other metrics'
scopes: rebase_refill, next_event, the scan loop and carry copies,
unscoped ops."""
from benchmark import scopes

read = scopes.reader(__file__, scopes.OTHER)
