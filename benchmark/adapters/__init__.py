"""One module per model family: the family's public configuration turned
into the program's own, and seeded weights in the program's layout."""
