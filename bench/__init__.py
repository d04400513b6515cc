"""On-chip benchmark of the budgeted kernel-SVM system (see ``run.py``)."""
