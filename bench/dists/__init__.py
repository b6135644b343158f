"""Distributions of traffic mixes, one file each, found by the ``dist``
named in a mix's parameters (``bench/gen.py``)."""
