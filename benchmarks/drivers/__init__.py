"""One window loop per kind of traffic: <kind>.py, chosen by the mix's ``kind``."""
