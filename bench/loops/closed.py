"""Closed loop: a client sends its next query only after its last answer
is ready, until the window closes."""
import time


def drive(ask, stream, client: int, deadline: float, record) -> None:
    while time.perf_counter() < deadline:
        q, _ = ask(next(stream), client)
        record(q)
