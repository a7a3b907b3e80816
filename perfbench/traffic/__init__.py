"""General generators, one module each; a cell's file holds the parameters."""
