"""One module per kind of cell; a cell names its kind in its own file."""
