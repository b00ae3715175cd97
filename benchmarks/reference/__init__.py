"""Plain float32 references, one a file (``r2d2.py``), each found by the name
its configurations give it, and the comparison that decides ``correct``
(``check.py``)."""
