"""Drivers, one file per kind of job, found by the traffic file's ``driver``."""
