"""The output comparison that decides a run's ``correct``: what the timed
path served, teacher-forced through the plain reference (``reference.py``)."""
