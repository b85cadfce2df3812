"""Bytes the plan's collectives send out of one device per product, in GB
(10**9 bytes): the plan's ``plan.wire_bytes`` gauge, read from the
program's registry."""
from harness import program


def read(run):
    sent = program.gauge(program.counters(), "plan.wire_bytes")
    return None if sent is None else sent / 1e9
