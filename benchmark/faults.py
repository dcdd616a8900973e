"""Faults planted in the timed path, for showing that `correct` fails:
wrappers around the program's `window_step` (benchmark/tests/
test_control.py at tiny size, benchmark/proof.py on the chip).

- "unchanged": the step returns the state it was given;
- "half": half of the hosts' deliveries are left out of the respawn;
- "altered": one host's delivered packets come out with seq + 1."""

KINDS = ("unchanged", "half", "altered")


def planted(kind: str, real):
    if kind not in KINDS:
        raise ValueError(f"unknown fault {kind!r}; one of {KINDS}")

    def window_step(state, *a, **kw):
        out = real(state, *a, **kw)
        new, delivered, nxt = out[:3]
        delivered = dict(delivered)
        if kind == "unchanged":
            new = state
        elif kind == "half":
            n = delivered["mask"].shape[0]
            delivered["mask"] = delivered["mask"].at[n // 2:].set(False)
        else:
            delivered["seq"] = delivered["seq"].at[0].add(1)
        return (new, delivered, nxt) + tuple(out[3:])

    return window_step
