"""Chip benchmark of the serving and live-handoff path (see README.md)."""
