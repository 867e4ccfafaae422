"""Requests whose f+1 matching signed committed replies arrived inside
the window, over the window's length."""

from reducers._window import completed_in_window


def reduce(run: dict, args: dict):
    return completed_in_window(run) / run["seconds"]
