"""Runners, one per kind of traffic (the ``kind`` key of a traffic file)."""
