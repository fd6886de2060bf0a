"""One module per plain reference, loaded by the name a configuration gives
under ``reference`` (``dense`` where it gives none), as readers are loaded by
the name a metric's file gives. A family that the dense block does not
describe brings its own module in the PR that adds it. The interface is the
docstring of ``dense.py``."""
