"""One module per model family: builds the program's own objects for a
cell (the way the program's entry points do) from the configuration and
traffic files, and compares them with the family's plain reference."""
