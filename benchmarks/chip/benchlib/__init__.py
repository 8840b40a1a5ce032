"""The chip benchmark's harness: cell discovery, inputs from a seed, what
every traffic loop shares, the measured window, the trace reduction and
the comparison that decides ``correct``.  Nothing here is imported by the
program under test."""

import sys as _sys


def say(msg: str) -> None:
    """One progress line on standard error (never the result's stream)."""
    print(f"bench: {msg}", file=_sys.stderr, flush=True)
