"""Arrival loops of the endpoint cells, one module per mix ``loop``; each has
``run(cell, window, requests)``."""
