"""The benchmark of the GriT-DBSCAN service on the chip (``main.py``)."""
