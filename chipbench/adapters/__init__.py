"""Adapters: the only files of the benchmark that import the program.

One per model family (the ``family`` key of a configuration file). An adapter
turns the configuration's published sizes into the program's own module and
step, and says how to make a batch; it computes nothing that is judged.
"""
