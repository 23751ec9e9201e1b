"""Plain reference of GeoFF's workflow simulation for the Fig-4 chain.

The shared DAG recurrence of ``bench/sweep_reference.py`` (importing
nothing of the program) over the chain check -> virus -> ocr -> e_mail:
the configuration lists no ``edges``, so each step reads the one before
it, every join is over one in-edge, the poke reaches step v after v
messages, and the last step is the only sink.
"""

from bench.sweep_reference import scorer_totals, sweep_totals, transfer_s

__all__ = ["scorer_totals", "sweep_totals", "transfer_s"]
