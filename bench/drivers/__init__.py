"""Drivers, one per traffic kind (``bench/traffic/*.json`` ``kind``)."""
