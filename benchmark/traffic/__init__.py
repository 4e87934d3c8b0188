"""The one traffic generator and each mix as a data file."""
