"""The chip benchmark's harness: traffic, serving loop, spans, trace
reduction, roofline arithmetic and the reference that decides ``correct``.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under ``bench/configs``,
``bench/traffic`` and ``bench/metrics``; this package finds them by the
names ``BENCHMARK.json`` gives.  Nothing here imports the program under
test except ``serve.py``, which drives it through its public entry points.
"""
