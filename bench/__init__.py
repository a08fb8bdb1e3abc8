"""Chip benchmark of the streaming KWS server (see PERF.md)."""
