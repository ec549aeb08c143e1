"""One module per arrival process, found by the ``process`` a traffic
file names: ``times(spec, seconds, rate, rng)`` gives the sorted arrival
offsets of one window, ``round(rate * seconds)`` of them, in
``[0, seconds)``. A new process is a new file here."""
