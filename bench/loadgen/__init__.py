"""Traffic generators, one module per ``kind`` of a traffic file.

Each exposes ``run(send, traffic, base_spec, rng, t0, seconds)`` and
returns the list of request records it made.  ``send(spec, record,
stop_at)`` streams one request into its record (see ``bench.run``).
"""
