"""From a profiler capture to numbers: ``reduce.py`` (self time per operation,
busy union, idle gaps laid to host spans; rows of busy time by a
configuration's scope table under ``scopes/``), ``hlo_names.py`` (the scope
path of each operation, from the HLO the capture keeps), ``dump.py`` (look at
a capture by hand), ``xspace_text.py`` (captures as text, for fixtures)."""
