"""One module a model family: everything the harness knows about a
model's shape it asks of the family its configuration names."""
