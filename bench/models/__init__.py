"""One file per model type (the configuration file's ``model_type``):
the FFN of the plain reference.  Attention, norms and the head are
shared in ``bench/reference.py``."""
