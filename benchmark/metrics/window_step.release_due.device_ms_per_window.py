"""Device ms per window of the chain ops in window_step's `release_due`
scope: the due split and presentation sort."""
from benchmark import scopes

read = scopes.reader(__file__, "release_due")
