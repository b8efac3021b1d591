"""Families: one file per model family, named by a configuration's ``family``."""
