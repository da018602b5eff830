"""Drivers, one per traffic ``kind``. A driver has ``SPANS`` (the host
spans it opens), ``setup()``, ``window(seconds)``,
``end_to_end(window_s)``, ``report()``, ``free()`` and ``check()``, and
the counts ``attempted`` and ``failed``."""
