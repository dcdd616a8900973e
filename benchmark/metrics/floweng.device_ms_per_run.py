"""Device ms per run of the flow engine: the self time of every device
op in the window (the window programs, the completion probes, world
builds and eager ops; the cell runs nothing else on the device) over the
runs the window made."""
from benchmark import flowscopes

read = flowscopes.reader(__file__)
