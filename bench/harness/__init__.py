"""The chip benchmark's harness: the cells' description (``spec``), the
configurations (``models``), the traffic generator (``traffic``), set-up and
window (``driver``), the plain reference and its control (``reference``),
the check (``check``), chip peaks (``peaks``), the trace reduction
(``xtrace``) and one run of a cell (``runner``).  What one model family
needs (its sizes, FLOP count and reference layer) sits in
``bench/families/<family>.py``."""
