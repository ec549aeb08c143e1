"""One module per length distribution, found by the ``dist`` a traffic
file names: ``draw(spec, n, rng)`` gives ``n`` whole lengths. Every spec
states its own ``lo`` and ``hi`` (inclusive), which the runner reads to
know the widest shape the traffic can reach. A new distribution is a
new file here."""
