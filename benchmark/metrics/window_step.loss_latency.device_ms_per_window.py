"""Device ms per window of the chain ops in window_step's `loss_latency`
scope: the loss draw and the latency lookup."""
from benchmark import scopes

read = scopes.reader(__file__, "loss_latency")
