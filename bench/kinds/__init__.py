"""Kinds of load, one module each, named by a traffic mix's ``kind``
(see ``bench/traffic.py``)."""
