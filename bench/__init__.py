"""Chip benchmark of the serving stack: one cell per run, driven by data.

``bench/run.py`` is the entry point; see its docstring.
"""
