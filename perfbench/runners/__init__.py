"""Runners, found by the name a cell's file gives (``"runner"``)."""
