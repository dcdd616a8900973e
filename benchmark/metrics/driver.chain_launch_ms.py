"""Mean host ms of the program's `chain.launch` spans in the window."""
from benchmark import scopes

read = scopes.launch_ms
