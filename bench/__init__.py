"""The chip benchmark of the stencil engine (see ``run.py``)."""
